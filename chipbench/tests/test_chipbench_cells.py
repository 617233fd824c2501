"""Every cell rehearsed end to end on the CPU at a tiny size, the fault
tests and the control at that size, the command's refusal to measure
without a chip, and ``BENCHMARK.json`` against the files it names."""
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import harness, peaks  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the peak table; the rehearsal's shares are
    of a stand-in and mean nothing."""
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        peaks.Peaks(1e12, 1e11, "CPU rehearsal stand-in"))


def tiny(workload: str) -> harness.CellSpec:
    spec = harness.cell_spec(ROOT, workload)
    if spec.config["driver"] == "pooled_lookup":
        spec.config.update(num_embeddings_per_feature=[64, 3, 40, 200],
                           multi_hot_sizes=[3, 1, 2, 5], batch_size=8)
        spec.traffic["pool"] = 2
    else:
        spec.config["model"].update(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
            d_ff=128, vocab_size=256, dtype="float32", attn_chunk=8)
        spec.config["serving"].update(batch_slots=2, max_len=64)
        spec.traffic.update(
            clients=2, pool=6,
            prompt={"median": 10, "sigma": 0.5, "min": 4, "max": 20},
            output={"median": 5, "sigma": 0.5, "min": 2, "max": 10})
    return spec


def control_size(spec: harness.CellSpec) -> harness.CellSpec:
    """For the LM control: the published d_model and head count, so that
    the logits have the cell's scale, over 4 layers and ~100 served
    tokens."""
    if spec.config["driver"] == "lm_serving":
        spec.config["model"].update(
            num_layers=4, d_model=2560, num_heads=32, num_kv_heads=32,
            d_ff=512, vocab_size=2048, dtype="bfloat16")
        spec.traffic["output"] = {"median": 24, "sigma": 0.3, "min": 16,
                                  "max": 32}
    return spec


def run_tiny(workload, capsys, seconds=1.0, trace=False, control=False):
    spec = tiny(workload)
    if control:
        spec = control_size(spec)
        return harness.drive(spec, 2 ** 33 + 1, seconds, trace,
                             devices=jax.devices()[:1],
                             t0=time.perf_counter(), control=True)[1]
    capsys.readouterr()
    rc = harness.run_spec(spec, 2 ** 33 + 1, seconds, trace,
                          devices=jax.devices()[:1], t0=time.perf_counter())
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["dlrm-v2.zipf", "stablelm-3b.chat"])
def test_cell_rehearsal(workload, capsys):
    line = run_tiny(workload, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    cell = harness.cell_spec(ROOT, workload)
    assert set(line["metrics"]) == {m["name"] for m in
                                    cell.metrics("end_to_end")}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


def test_traced_rehearsal_reports_per_layer_metrics(capsys):
    line = run_tiny("stablelm-3b.chat", capsys, trace=True)
    assert line["correct"] is True
    # the CPU has no device plane: only host-side readings appear
    assert {"compile_s", "wave_ms.lm", "ttft_p50_ms.lm", "step_mfu.lm"} \
        <= set(line["metrics"])
    assert "device_idle_share.lm" not in line["metrics"]
    assert "busy_s" in line["device"] and "breakdown" in line


def test_traced_rates_leave_out_the_profilers_stop(monkeypatch):
    """The seconds the profiler takes to write its trace out fall inside
    the window; whole-window rates of work divide by the rest."""
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.3)
    run, _ = harness.drive(tiny("stablelm-3b.chat"), 2 ** 33 + 3, 1.5,
                           True, devices=jax.devices()[:1],
                           t0=time.perf_counter())
    f = run.facts
    a, b = f["paused"]
    assert f["t_open"] < a < b < f["t_close"]
    assert f["measured_s"] == pytest.approx(f["window_s"] - (b - a))
    assert len(f["ttft_s"]) > 0


def test_pooled_answer_altered_is_not_correct(capsys, monkeypatch):
    from repro.core import executor
    result = executor.StepHandle.result

    def altered(self):
        outs = result(self)
        name = sorted(outs)[0]
        outs[name] = outs[name].at[0, 0].add(1.0)
        return outs
    monkeypatch.setattr(executor.StepHandle, "result", altered)
    line = run_tiny("dlrm-v2.zipf", capsys)
    assert line["correct"] is False
    assert line["checks"]["pool_err"]["value"] > \
        line["checks"]["pool_err"]["limit"]


def test_lm_token_altered_is_not_correct(capsys, monkeypatch):
    from repro.models import lm as lm_mod
    wave = lm_mod.LM.wave_step

    def altered(self, *a, **kw):
        logits, caches = wave(self, *a, **kw)
        # every slot's best logit moves to the next token id
        return jax.numpy.roll(logits, 1, axis=-1), caches
    monkeypatch.setattr(lm_mod.LM, "wave_step", altered)
    line = run_tiny("stablelm-3b.chat", capsys)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"]["value"] > \
        line["checks"]["token_gap_mean"]["limit"]


@pytest.mark.parametrize("workload,number", [
    ("dlrm-v2.zipf", "pool_err"), ("stablelm-3b.chat", "token_gap_mean")])
def test_control_fails_the_limit(workload, number, capsys):
    """The reference one precision below the configuration's, in the
    program's place, comes out not correct by the benchmark's own rule,
    while the program is correct."""
    from chipbench.control import judge
    line = judge(run_tiny(workload, capsys, control=True))
    assert line["correct"] is True, line["checks"]
    assert line["control_correct"] is False, line["control_checks"]
    assert set(line["control_checks"]) == set(line["checks"])
    mine, ctrl = line["checks"][number], line["control_checks"][number]
    assert ctrl["limit"] == mine["limit"]
    assert mine["value"] <= mine["limit"] < ctrl["value"]


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "dlrm-v2.zipf",
         "--seed", str(2 ** 33), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not TPUs" in p.stderr


def test_benchmark_names_its_files():
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "chipbench" / "drivers"
                / f"{cfg['driver']}.py").is_file()
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    names = [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for m in list(e2e.values()) + list(per_layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert (ROOT / "chipbench" / "metrics"
                / f"{m['name']}.py").is_file()
    for m in per_layer.values():
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (ROOT / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file()
        spec = harness.cell_spec(ROOT, w["name"])
        mine = {m["name"] for m in spec.metrics("end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layers = spec.metrics("per_layer")
        assert layers and all(m["moves"] in mine for m in layers)


def test_rehearsal_inputs_are_seeded():
    """The same seed gives the same traffic; another gives other keys."""
    from chipbench.generate import pooled_batches
    traffic = {"kind": "pooled_lookups", "alpha": 0.8, "pool": 2}
    a = pooled_batches([100, 7], [3, 2], 4, traffic, 2 ** 33 + 1)
    b = pooled_batches([100, 7], [3, 2], 4, traffic, 2 ** 33 + 1)
    c = pooled_batches([100, 7], [3, 2], 4, traffic, 5)
    assert all((a[i][t][1] == b[i][t][1]).all()
               for i in range(2) for t in range(2))
    assert not all((a[i][0][1] == c[i][0][1]).all() for i in range(2))
    assert np.array_equal(a[0][0][0], [0, 3, 6, 9, 12])
