"""End-to-end drills of ``serve.py --continuous`` (subprocess, full CLI).

Same idiom as the chaos drill in test_chaos.py: the serving binary runs in
its own interpreter (its own device topology, chaos controller, tracer),
and the test asserts on its report line and metrics-json — the artifacts
an operator actually sees.  The continuous-specific contracts:

  * the report names the scheduler mode (``mode=continuous``);
  * the admission ledger cross-foots with the row ledger
    (``serve.admission.admitted == retired + shed``; with no sheds,
    ``retired == serve.queries``);
  * mid-walk admissions survive a shard death bit-identically to the
    degraded (tombstoned surviving-corpus) oracle.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# serve creates --devices host devices only when the CPU platform is forced
_ENV = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}

_CLEAN = textwrap.dedent("""
    import json, os, subprocess, sys, tempfile
    tmp = tempfile.mkdtemp()
    mj = os.path.join(tmp, "m.json")
    tr = os.path.join(tmp, "t.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--devices", "1", "--corpus-per-device", "1200", "--dim", "48",
         "--index", "graph", "--continuous", "--requests", "4",
         "--batch", "8", "--ef", "16", "--k", "5",
         "--open-loop", "200", "--verify-graph-oracle",
         "--slo", "1:4", "--metrics-json", mj, "--trace", tr],
        capture_output=True, text=True, env={**os.environ,
                                             "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "mode=continuous" in r.stdout, r.stdout
    assert "verify: continuous engine (shards=1) bit-identical" in r.stdout
    m = json.load(open(mj))["metrics"]
    v = lambda k: m.get(k, {}).get("value")
    z = lambda k: v(k) or 0  # counters register lazily; missing == 0
    admitted = v("serve.admission.admitted")
    retired = v("serve.admission.retired")
    shed = z("serve.admission.shed")
    assert admitted and admitted == retired + shed, m
    # Clean run: every admitted row retires and is served, so the
    # admission ledger cross-foots with the row ledger exactly.
    assert shed == 0 and retired == v("serve.queries"), m
    assert m["serve.wave.depth"]["count"] == retired, m
    assert v("serve.admission.waves") > 0
    assert v("serve.retire.frontier") == retired, m
    assert v("serve.wave.occupancy") is not None
    assert os.path.getsize(tr) > 0, "empty trace artifact"
    ev = json.load(open(tr))
    names = {e.get("name") for e in ev.get("traceEvents", ev)}
    assert "continuous.wave" in names, sorted(names)[:40]
    print("OK continuous_clean")
""")

_CHAOS = textwrap.dedent("""
    import json, os, subprocess, sys, tempfile
    tmp = tempfile.mkdtemp()
    mj = os.path.join(tmp, "m.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--devices", "1", "--corpus-per-device", "1200", "--dim", "48",
         "--index", "graph", "--graph-shards", "2", "--continuous",
         "--requests", "5", "--batch", "8", "--ef", "16", "--k", "5",
         "--open-loop", "200", "--deadline-ms", "30000",
         "--chaos", "shard_death:shard=1:after=3",
         "--verify-degraded-oracle", "--metrics-json", mj],
        capture_output=True, text=True, env={**os.environ,
                                             "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "mode=continuous" in r.stdout, r.stdout
    assert ("verify-degraded: continuous admissions with dead shards [1] "
            "bit-identical") in r.stdout, r.stdout
    m = json.load(open(mj))["metrics"]
    v = lambda k: m.get(k, {}).get("value")
    z = lambda k: v(k) or 0
    assert v("serve.fault.shard_death") == 1, m
    assert v("serve.admission.admitted") == \\
        z("serve.admission.retired") + z("serve.admission.shed"), m
    assert v("serve.requests.submitted") == 5, m
    print("OK continuous_chaos")
""")


@pytest.mark.slow
def test_serve_continuous_clean_end_to_end():
    r = subprocess.run([sys.executable, "-c", _CLEAN],
                       capture_output=True, text=True, env=_ENV, cwd=".",
                       timeout=540)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK continuous_clean" in r.stdout


@pytest.mark.slow
def test_serve_continuous_survives_shard_death():
    r = subprocess.run([sys.executable, "-c", _CHAOS],
                       capture_output=True, text=True, env=_ENV, cwd=".",
                       timeout=540)
    assert r.returncode == 0, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "OK continuous_chaos" in r.stdout


def test_continuous_flag_requires_graph_index():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--continuous",
         "--devices", "1", "--corpus-per-device", "64", "--requests", "1"],
        capture_output=True, text=True, env=_ENV, cwd=".", timeout=120)
    assert r.returncode != 0
    assert "--continuous" in (r.stdout + r.stderr)
