"""The MLA + MoE serving cell rehearsed end to end on the CPU at a tiny
size, its control at the published attention widths, the roofline reader
on the rehearsal's facts, and ``moe_counts`` on hand-counted cases."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import harness, moe_counts, peaks, trace  # noqa: E402

CELL = "deepseek-v2-lite.long-answer"
SEED = 2 ** 33 + 1


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the peak table; the rehearsal's shares are
    of a stand-in and mean nothing."""
    monkeypatch.setitem(peaks.PEAKS, "cpu",
                        peaks.Peaks(1e12, 1e11, "CPU rehearsal stand-in"))


def tiny(control: bool = False) -> harness.CellSpec:
    """The cell with every width cut (or, for the control, the published
    attention widths and router over 3 layers, 4 held experts of width
    256, a vocabulary of 2048), 2 slots of 64 positions, short requests."""
    spec = harness.cell_spec(ROOT, CELL)
    if control:
        spec.config["model"].update(num_layers=3, d_ff=512, vocab_size=2048,
                                    experts_held=4, moe_d_ff=256)
        output = {"median": 24, "sigma": 0.3, "min": 16, "max": 32}
    else:
        spec.config["model"].update(
            num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
            head_dim=16, d_ff=96, vocab_size=256, num_experts=8,
            experts_held=4, experts_per_tok=2, num_shared_experts=1,
            moe_d_ff=32, kv_lora_rank=16, rope_head_dim=8, dtype="float32")
        output = {"median": 5, "sigma": 0.5, "min": 2, "max": 10}
    spec.config["model"]["attn_chunk"] = 8
    spec.config["serving"].update(batch_slots=2, max_len=64)
    spec.traffic.update(clients=2, pool=6, output=output,
                        prompt={"median": 10, "sigma": 0.5, "min": 4,
                                "max": 20})
    return spec


def test_moe_cell_rehearsal(capsys):
    capsys.readouterr()
    rc = harness.run_spec(tiny(), SEED, 1.0, False,
                          devices=jax.devices()[:1], t0=time.perf_counter())
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in harness.cell_spec(
        ROOT, CELL).metrics("end_to_end")} == \
        {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_moe_traced_rehearsal_reads_the_step_roofline():
    """A traced run's facts hold each window wave's least bytes and step
    span; the roofline reader takes those inside the traced part over the
    device's busy time (the CPU records no device plane: a stand-in
    summary, busy the whole traced part, is the device here)."""
    run, out = harness.drive(tiny(), SEED + 2, 1.0, True,
                             devices=jax.devices()[:1],
                             t0=time.perf_counter())
    f = run.facts
    assert out["failed"] == 0
    lo, hi = f["traced"]
    inside = [w for w in f["wave_bytes"] if w[0] >= lo and w[1] <= hi]
    assert inside and len(f["moe_waves"]) >= len(f["wave_bytes"])
    assert all(a >= t > 0 for _, a, t in f["moe_waves"])
    reader = harness.load_module(ROOT / "chipbench" / "metrics"
                                 / "step_hbm_roofline.moe.py")
    f["trace"] = trace.Summary(hi - lo, hi - lo, 1, {}, [], [])
    want = 100.0 * sum(b for *_, b in inside) / (1e11 * (hi - lo))
    assert reader.read(f) == pytest.approx(want)
    f["trace"] = trace.Summary(hi - lo, 0.0, 0, {}, [], [])
    assert reader.read(f) is None
    for name in ("step_mfu.lm", "wave_ms.lm", "ttft_p50_ms.lm"):
        value = harness.load_module(ROOT / "chipbench" / "metrics"
                                    / f"{name}.py").read(f)
        assert value is not None and value > 0, name


def test_moe_control_fails_the_limit(capsys):
    """The fp8 reference in the program's place comes out not correct by
    the cell's own limit, while the program is correct."""
    from chipbench.control import judge
    _, out = harness.drive(tiny(control=True), SEED, 1.0, False,
                           devices=jax.devices()[:1],
                           t0=time.perf_counter(), control=True)
    line = judge(out)
    assert line["correct"] is True, line["checks"]
    assert line["control_correct"] is False, line["control_checks"]
    mine = line["checks"]["token_gap_mean"]
    ctrl = line["control_checks"]["token_gap_mean"]
    assert mine["value"] <= mine["limit"] == ctrl["limit"] < ctrl["value"]


TINY = {"d_model": 4, "num_heads": 2, "head_dim": 2, "kv_lora_rank": 3,
        "rope_head_dim": 2, "d_ff": 5, "moe_d_ff": 3, "num_experts": 6,
        "num_shared_experts": 2, "num_layers": 3, "first_k_dense": 1,
        "vocab_size": 10, "dtype": "bfloat16"}


def test_moe_counts_by_hand():
    # one MLA layer: wq 4*2*4, w_dkv 4*3, w_uk + w_uv 2*3*2*2, w_kr 4*2,
    # wo 2*2*4
    assert moe_counts._attn_params(TINY) == 32 + 12 + 24 + 8 + 16
    assert moe_counts.expert_params(TINY) == 36
    # 3 attention layers, 1 dense MLP (3*4*5), 2 routers (4*6) and 2 x 2
    # shared experts, the head (10*4)
    assert moe_counts.token_matmul_params(TINY) == \
        3 * 92 + 60 + 2 * (24 + 2 * 36) + 40
    # bf16 weights but the routers (float32); norms: 7 of d, 3 of the latent
    assert moe_counts.step_weight_bytes(TINY) == \
        2 * (568 - 48 + 28 + 9) + 4 * 48
    assert moe_counts.latent_row_bytes(TINY) == 3 * 5 * 2
    # 2 fed micro-steps, 3 tokens (embedding rows), 4 touched experts,
    # contexts adding to 9
    assert moe_counts.wave_least_bytes(TINY, 2, 3, 4, 9) == \
        2 * 1306 + 3 * 8 + 4 * 72 + 9 * 30
    # 3 tokens with 5 held picks; scores 2*(2+2) and values 2*2 per head
    assert moe_counts.wave_flops(TINY, 3, 5, 9) == \
        2 * (568 * 3 + 36 * 5) + 2 * 3 * 2 * 6 * 9
