"""Whole runs of small cells on the CPU: the result line's contract, the
comparison that decides ``correct`` and the faults it has to catch, the
refusal to run off the chip, and cells found from data alone."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness

REPO = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4242


def _run(checkout, devices, workload, **kw):
    return harness.run(checkout, workload, SEED, 2.0, kw.pop("trace", False),
                       devices, time.time(), **kw)


def _scoring(svc):
    eng = svc.engine
    return eng, ("_join_pool" if eng.doc_cache is not None else "_join_raw")


def _alter_one_answer(svc):
    eng, attr = _scoring(svc)
    fn = getattr(eng, attr)
    setattr(eng, attr, lambda *a: fn(*a).at[0].add(1.0))


def _leave_out_half(svc):
    eng, attr = _scoring(svc)
    fn = getattr(eng, attr)

    def half(*a):
        s = fn(*a)
        h = s.shape[0] // 2
        return s.at[h:].set(s[:h])

    setattr(eng, attr, half)


@pytest.mark.parametrize("workload", ["dense", "paged"])
def test_cell_runs_and_prints_the_contract(checkout, program, workload):
    out = _run(checkout, program, workload)
    json.dumps(out)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 12
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]} - {"peak_hbm_gib"}
    assert want <= set(out["metrics"])
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    dev = out["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name
    assert not (checkout / "bench" / ".work" / workload).exists()


def test_traced_run_reports_per_layer_metrics(checkout, program):
    out = _run(checkout, program, "paged", trace=True)
    assert out["correct"] is True
    names = set(out["metrics"])
    assert {"p95_latency_ms", "admit_wait_ms", "stage_ms_per_batch",
            "h2d_kib_per_doc", "doc_cache_hit_rate",
            "score_ms_per_batch"} <= names
    # nothing on the CPU is a device: the trace's readers stay silent
    assert not names & {"score_step_mfu", "join_attention_roofline",
                        "device_idle_share"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half])
@pytest.mark.parametrize("workload", ["dense", "paged"])
def test_faults_come_out_not_correct(checkout, program, workload, fault):
    out = _run(checkout, program, workload, wrap_service=fault)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("workload", ["dense", "paged"])
def test_control_comes_out_not_correct(checkout, program, workload):
    """The control, the reference computed in fp8 (one precision below the
    bfloat16 the configuration computes in), read on the same sample as
    the program, fails the limit that the program's run meets.  Where the
    index stores int8 K/V the int4 K/V reading is taken too."""
    import cells

    cfg = cells.load(checkout, workload).config
    out = _run(checkout, program, workload,
               controls=harness.controls_for(cfg))
    assert out["correct"] is True
    assert set(out["controls"]) == set(harness.controls_for(cfg))
    assert ("int4_kv" in out["controls"]) == (workload == "paged")
    fp8 = out["controls"]["fp8"]
    assert any(fp8[k] > lim for k, lim in cfg["check"].items())


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dense", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_exits_without_a_tpu(checkout):
    p = _cli(checkout)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_exits_without_the_program(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "l6_docs.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_new_traffic_and_metric_files_are_found(checkout, program, tmp_path):
    """A later change adds a cell and a per-layer metric by adding files and
    entries: nothing of the harness is edited."""
    root = tmp_path / "co"
    shutil.copytree(checkout, root, symlinks=True,
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*.py")}
    spec = json.loads((root / "bench" / "traffic" / "small_dense.json")
                      .read_text())
    spec["arrivals"]["rate_per_s"] = 3.0
    spec["candidates"]["per_request"] = 5
    (root / "bench" / "traffic" / "small_sparse.json").write_text(
        json.dumps(spec))
    (root / "bench" / "metrics" / "rows_per_request.py").write_text(
        "def read(ctx):\n"
        "    return ctx.stats['n_rows'] / len(ctx.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "sparse", "config": "small_dense",
                               "traffic": "small_sparse", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "rows_per_request", "unit": "rows",
                               "better": "lower", "source": "program_counter",
                               "layer": "plan and stage",
                               "moves": "p50_latency_ms",
                               "workloads": ["sparse"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _run(root, program, "sparse", trace=True)
    assert out["correct"] is True and out["attempted"] == 6
    assert out["metrics"]["rows_per_request"]["value"] == 5
    assert before == {p: p.read_bytes() for p in before}
