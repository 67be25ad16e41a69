"""Whole runs of every configuration at a tiny size, on the CPU in
interpret mode, and the faults each cell must catch.

The tiny sizes keep every width of the configuration (256-d rows, 128-d
blocks, 64-row batches) and cut only the corpus, the query pool and the
traffic.
"""

import json

import pytest

import faults
import run

TINY = {"corpus_per_device": 2048, "wave": 1024, "estimator_sample": 2048,
        "data": {"mixture_seed": 0, "n_modes": 16, "decay": 0.05,
                 "query_jitter": 0.1, "query_pool": 192}}
BULK = {"clients": 2, "request_rows": [8, 24]}
ONLINE = {"rate_rps": 20.0}
CELLS = {
    "flat-deep256-bulk": (TINY, BULK),
    "flat-deep256-online": (TINY, ONLINE),
}


def go(capsys, workload, *, seed=3_000_000_019, trace=0, fault=None, mix=None,
       cfg=None):
    tiny, base = CELLS[workload]
    cfg = dict(tiny, **(cfg or {}))
    mix = dict(base, **(mix or {}))
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], allow_cpu=True,
                  overrides={"cfg": dict(cfg), "traffic": dict(mix)},
                  fault=fault)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_every_cell_runs_and_is_correct(capsys, workload):
    res, out = go(capsys, workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == run.load_cell(workload).chips
    bench = json.load(open(run.ROOT + "/BENCHMARK.json"))
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert "programs lowered inside the window: 0" in out.out
    # The compared numbers are the last lines on standard error and the
    # last key of the result line.
    assert list(res)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")


def test_traced_run_reports_per_layer_metrics(capsys):
    res, _ = go(capsys, "flat-deep256-online", trace=1)
    assert res["correct"]
    assert "sched.batch_fill" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", ["flat-deep256-bulk", "flat-deep256-online"])
@pytest.mark.parametrize("fault", ["control", "answer_altered", "half_batch"])
def test_faults_come_out_not_correct(capsys, workload, fault):
    # Enough arrivals that a batch holds several real rows; the control
    # replaces the program, so it can afford a corpus nearer the cell's.
    res, _ = go(capsys, workload, fault=faults.FAULTS[fault],
                mix={"rate_rps": 200.0} if "online" in workload else None,
                cfg={"corpus_per_device": 65536} if fault == "control" else None)
    assert not res["correct"], res["checks"]


def test_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "flat-deep256-bulk", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 3
    assert out.out.strip().splitlines()[-1].startswith("bench: workload=")
    assert "no TPU" in out.err


def test_config_files_match_the_benchmark():
    bench = json.load(open(run.ROOT + "/BENCHMARK.json"))
    for conf in bench["configs"]:
        cfg = json.load(open(run.ROOT + "/" + conf["file"]))
        assert cfg["name"] == conf["name"]
        assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
