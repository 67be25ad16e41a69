"""serve.py --method regression (satellite of the estimator-spec PR).

The CLI must serve every expressible method through the fused megakernel
route with full observability — the report line says the route, the
metrics snapshot carries the ``dco.method.<name>`` tag cross-footed with
``serve.queries``, and the stdlib schema gate accepts the file — and must
refuse the inexpressible fixed-dim baselines BY NAME before any engine
builds (a named UnsupportedMethodError, not a mid-search shape error).

Subprocess-driven on purpose: this is the CI serve smoke's contract,
exercised end to end (argv -> engines -> metrics file) the way operators
hit it.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_serve(cwd, *args):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args],
        capture_output=True, text=True, env=env, cwd=str(cwd), timeout=570)


def test_serve_adsampling_fused_reports_method_and_ledger(tmp_path):
    mpath = tmp_path / "serve_metrics.json"
    p = _run_serve(
        tmp_path,
        "--devices", "1", "--method", "adsampling", "--quant", "int8",
        "--fused", "on", "--corpus-per-device", "4096", "--dim", "64",
        "--requests", "2", "--batch", "16", "--metrics-json", str(mpath))
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    # the report line names the method and the megakernel route, and
    # carries the demand-paged fetch accounting
    assert "method=adsampling" in p.stdout
    assert "fused=megakernel" in p.stdout
    assert "s2_skip_rate=" in p.stdout

    doc = json.loads(mpath.read_text())
    m = doc["metrics"]
    tags = sorted(k for k in m if k.startswith("dco.method."))
    assert tags == ["dco.method.adsampling"], tags
    queries = m["serve.queries"]["value"]
    assert queries > 0
    assert m["dco.method.adsampling"]["value"] == queries
    assert m["dco.fetched.bytes"]["value"] > 0
    assert m["dco.semantic.bytes"]["value"] > 0
    assert m["ivf.fused.merge_rounds"]["value"] > 0

    # the stdlib schema gate (CI's check) must accept the same snapshot
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "check_metrics_schema.py"),
         str(mpath)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.parametrize("bad", ["pca_fixed", "rp_fixed"])
def test_serve_refuses_inexpressible_method_by_name(tmp_path, bad):
    p = _run_serve(
        tmp_path,
        "--devices", "1", "--method", bad, "--quant", "int8",
        "--corpus-per-device", "256", "--dim", "32",
        "--requests", "1", "--batch", "8")
    assert p.returncode != 0, p.stdout[-1000:]
    assert "UnsupportedMethodError" in p.stderr, p.stderr[-2000:]
    assert bad in p.stderr
