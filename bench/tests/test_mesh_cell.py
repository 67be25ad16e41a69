"""The four-chip cell ``flat-deep256x4-bulk``: a whole run at a tiny size on
four host devices, in interpret mode, with the fault ``correct`` must catch
on a mesh; and the reader of its exchange, on hand-built traces.

The run goes in a subprocess, which creates the four devices before JAX
starts (this process keeps one).  The tiny size keeps every width of the
configuration (256-d rows, 128-d blocks, 64-row batches) and cuts only the
corpus, the query pool and the traffic."""

import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

import run
from tracing import Op, Trace

WORKLOAD = "flat-deep256x4-bulk"
MS = 1e6  # trace times are nanoseconds

_SCRIPT = textwrap.dedent("""
    import contextlib, io, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    bench, src, workload = sys.argv[1:4]
    sys.path[:0] = [bench, src]
    import engine as engines
    import faults
    import run

    SEED = 3_000_000_021
    TINY = {"corpus_per_device": 2048, "wave": 1024, "estimator_sample": 2048,
            "data": {"mixture_seed": 0, "n_modes": 16, "decay": 0.05,
                     "query_jitter": 0.1, "query_pool": 192}}
    BULK = {"clients": 2, "request_rows": [8, 24]}

    def merge_left_out(_, *, blocks, cfg):
        # The program over chip 0's rows alone: the answers the step gives
        # when the merge with the other three chips is left out.
        dev = blocks[0].devices().pop()
        return engines.build(cfg, blocks[:1], [dev], SEED, interpret=True)

    merge_left_out.replaces_program = True

    # The control replaces the program, so it can afford a corpus nearer
    # the cell's.
    for name, fault, cfg in [
            ("program", None, {}),
            ("merge_left_out", merge_left_out, {}),
            ("control", faults.control, {"corpus_per_device": 16384})]:
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", str(SEED),
                           "--seconds", "1", "--trace", "0"], allow_cpu=True,
                          overrides={"cfg": dict(TINY, **cfg),
                                     "traffic": dict(BULK)}, fault=fault)
        print("RESULT", name, rc, out.getvalue().strip().splitlines()[-1])
""")


@pytest.fixture(scope="module")
def results():
    bench = os.path.dirname(os.path.abspath(run.__file__))
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT, bench,
         os.path.join(os.path.dirname(bench), "src"), WORKLOAD],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            _, name, rc, text = line.split(" ", 3)
            assert rc == "0", line
            out[name] = json.loads(text)
    return out


def test_cell_runs_on_four_devices_and_is_correct(results):
    res = results["program"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4 == run.load_cell(WORKLOAD).chips
    assert set(res["metrics"]) == {"qps", "recall_at_10", "setup_s"}


@pytest.mark.parametrize("fault", ["merge_left_out", "control"])
def test_faults_come_out_not_correct(results, fault):
    res = results[fault]
    assert not res["correct"], res["checks"]


def trace_of(ops):
    return Trace(ops=ops, spans=[Op("bench.window", 0, 100 * MS)],
                 window=(0.0, 100 * MS))


def ctx(tr, chips):
    return SimpleNamespace(trace=tr, chips=chips, stats={"batches": 4},
                           cfg={"route": "flat"}, log=lambda m: None)


def test_exchange_reader():
    read = run.reader("exchange.ms_per_batch")
    tr = trace_of({
        # Chip 0: the seed's pmin (2 ms), the merge's gathers (3 ms, 1 ms
        # of it overlapping), the kernel and the fusion that reads a
        # gather's result.
        0: [Op("pmin.7", 10 * MS, 12 * MS),
            Op("ivf_scan_kernel_call.1", 12 * MS, 40 * MS),
            Op("all-gather.12", 40 * MS, 42 * MS),
            Op("all-gather.13", 41 * MS, 43 * MS),
            Op("negate_bitcast_fusion", 43 * MS, 44 * MS)],
        # Chip 1: an asynchronous pair, a fusion named after its
        # collective, and a window edge: 95-105 counts 5 ms.
        1: [Op("all-reduce-start.1", 10 * MS, 11 * MS),
            Op("all-reduce-done.1", 20 * MS, 24 * MS),
            Op("all-gather-fusion.3", 30 * MS, 31 * MS),
            Op("all-gather.12", 95 * MS, 105 * MS)],
    })
    # Chip 0: 2 + 3 = 5 ms; chip 1: 1 + 4 + 1 + 5 = 11 ms; mean 8 ms over
    # 4 batches.
    assert read(ctx(tr, 2)) == pytest.approx(2.0)
    # One chip, or no collective on any chip, reads nothing.
    assert read(ctx(tr, 1)) is None
    quiet = trace_of({0: [Op("ivf_scan_kernel_call.1", 0, 50 * MS)],
                      1: [Op("fusion.1", 0, 50 * MS)]})
    assert read(ctx(quiet, 2)) is None
