"""The benchmark's own counts, peak table and trace reduction (CPU only)."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from chipbench import flops, harness, peaks, tracing
from chipbench.generators import train_tokens

GRANITE = harness.load_json(os.path.join(harness.BENCH_DIR, "configs", "granite-3-8b.json"))
TESTDATA = os.path.join(harness.BENCH_DIR, "testdata")


def test_granite_matmul_parameters_by_hand():
    d, ff, vocab, layers = 4096, 12800, 49155, 2
    q = o = d * 32 * 128
    k = v = d * 8 * 128
    per_layer = q + k + v + o + 3 * d * ff  # 199,229,440
    assert per_layer == 199_229_440
    assert flops.dense_matmul_params(GRANITE) == layers * per_layer + d * vocab
    assert flops.dense_matmul_params(GRANITE) == 599_797_760


def test_granite_train_flops_per_token_by_hand():
    # attention: QK^T and PV, 2 * 32 heads * 128 each per key, over the
    # causal half of 2,048 keys (2,049 / 2 on average), in 2 layers
    attn_fwd = 2 * 2 * (2 * 32 * 128) * 2049 / 2
    want = 6 * 599_797_760 + 3 * attn_fwd
    assert flops.dense_train_flops_per_token(GRANITE, 2048) == pytest.approx(want, rel=1e-12)
    assert 3.69e9 < want < 3.71e9


def test_bocd_step_bytes_at_the_fleet_shape():
    # log_r, mu, beta read and written (6 x 32 x 8,192 float32) + x in, p0 out
    assert flops.bocd_step_bytes(32, 8192) == 6 * 32 * 8192 * 4 + 2 * 8192 * 4
    assert flops.bocd_step_bytes(32, 8192) == 6_356_992


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    row = peaks.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


def _hand_trace() -> dict:
    ms = 1e6  # ns per ms
    return {
        "device": {"/device:TPU:0": [
            ["fusion.1", 1 * ms, 1.5 * ms],      # 1-2.5
            ["bocd_step.1", 2 * ms, 2 * ms],     # 2-4, overlaps: union 1-4
            ["copy.2", 6 * ms, 1 * ms],          # 6-7
            ["outside", 12 * ms, 1 * ms],        # after the window
        ]},
        "host": [
            [tracing.WINDOW_SPAN, 0.0, 10 * ms],
            ["chipbench.tick", 0.0, 5 * ms],
            ["chipbench.generate", 4 * ms, 2 * ms],  # covers the gap 4-6
        ],
    }


def test_reduce_a_hand_made_trace():
    s = tracing.reduce(_hand_trace())
    assert s.window_s == pytest.approx(10e-3)
    assert s.busy_s == pytest.approx(4e-3)  # 1-4 and 6-7
    assert s.idle_share == pytest.approx(0.6)
    assert s.kernel_s("bocd_step") == pytest.approx(2e-3)
    assert "outside" not in s.op_s
    idle = s.idle_by_host_s
    assert idle["chipbench.tick"] == pytest.approx(1e-3)      # 0-1
    assert idle["chipbench.generate"] == pytest.approx(2e-3)  # 4-6
    assert idle["idle (no host span)"] == pytest.approx(3e-3)  # 7-10
    b = s.breakdown()
    assert b["device_ops"][0] == ["bocd_step.1", pytest.approx(2e-3)]
    assert len(b["idle_gaps"]) == 3


def test_reduce_refuses_a_trace_without_its_window_or_device():
    ev = _hand_trace()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        tracing.reduce(ev)
    ev = _hand_trace()
    ev["device"] = {}
    with pytest.raises(ValueError):
        tracing.reduce(ev)


RECORDED = sorted(f for f in os.listdir(TESTDATA) if f.startswith("trace_"))


def test_there_are_recorded_chip_traces():
    assert len(RECORDED) >= 2


@pytest.mark.parametrize("name", RECORDED)
def test_reduce_a_recorded_chip_trace(name):
    """The reduction against a plain recount of the same events: busy time
    on a 100 ns grid, each op's self time from its own children."""
    with open(os.path.join(TESTDATA, name)) as f:
        ev = json.load(f)
    s = tracing.reduce(ev)
    w0, w1 = tracing.window_bounds(ev["host"])
    (ops,) = ev["device"].values()
    grid = np.zeros(int((w1 - w0) / 100) + 1, bool)
    for _, a, d in ops:
        lo, hi = max(a, w0), min(a + d, w1)
        if hi > lo:
            grid[int((lo - w0) / 100):int(np.ceil((hi - w0) / 100))] = True
    assert s.busy_s == pytest.approx(grid.sum() * 100e-9, rel=2e-2)
    assert 0 < s.busy_s <= s.window_s
    assert sum(s.idle_by_host_s.values()) == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
    # self time: an op's span less the spans of ops nested inside it
    inside = [(n, max(a, w0), min(a + d, w1)) for n, a, d in ops if min(a + d, w1) > max(a, w0)]
    for name, a, b in inside[:50]:
        children = [(x, y) for m, x, y in inside if (x, y) != (a, b) and a <= x and y <= b
                    and not any(a <= x2 <= x and y <= y2 <= b and (x2, y2) not in ((a, b), (x, y))
                                for _, x2, y2 in inside)]
        own = (b - a) - sum(y - x for x, y in children)
        if sum(1 for m, _, _ in inside if m == name) == 1:
            assert s.op_s[name] == pytest.approx(own * 1e-9, rel=1e-6, abs=1e-12)


def test_the_screen_kernel_is_found_by_its_stable_name():
    with open(os.path.join(TESTDATA, "trace_v5e_fleet_screen.json")) as f:
        ev = json.load(f)
    s = tracing.reduce(ev)
    w0, w1 = tracing.window_bounds(ev["host"])
    (ops,) = ev["device"].values()
    want = sum(min(a + d, w1) - max(a, w0) for n, a, d in ops
               if n.startswith("bocd_step.") and min(a + d, w1) > max(a, w0))
    assert want > 0
    assert s.kernel_s("bocd_step") == pytest.approx(want * 1e-9, rel=1e-9)


def test_nested_ops_count_once():
    ms = 1e6
    ev = {"device": {"/device:TPU:0": [["while.1", 0.0, 10 * ms], ["fusion.2", 2 * ms, 3 * ms],
                                         ["fusion.3", 6 * ms, 1 * ms]]},
          "host": [[tracing.WINDOW_SPAN, 0.0, 20 * ms]]}
    s = tracing.reduce(ev)
    assert s.busy_s == pytest.approx(10e-3)
    assert s.op_s["while.1"] == pytest.approx(6e-3)
    assert s.op_s["fusion.2"] == pytest.approx(3e-3)
    assert s.kernel_s("fusion") == pytest.approx(4e-3)


def test_train_tokens_copy_the_program_stream():
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig, make_batch

    cfg = get_config("granite-3-8b").smoke()
    data = DataConfig(seq_len=48, global_batch=4, slots=2, dp_groups=2, seed=2**45 + 3)
    want = make_batch(cfg, data, 0)
    toks, labels = train_tokens(data.seed, 2, 2, 48, cfg.vocab_size)
    assert np.array_equal(toks, want["tokens"])
    assert np.array_equal(labels, want["labels"])
