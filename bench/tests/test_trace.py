"""The trace reduction on a trimmed copy of a real chip trace.

``bench/fixtures/trace_gpt2s_1chip_sync.json.gz`` is the kept form
(``devtrace.load``) of a ``--trace 1`` run of ``gpt2s-1chip-h50`` on one
TPU v5 lite, cut with ``devtrace.trim`` to three steps of the window, the
last of which runs the outer sync. The per-layer readers must give the
same numbers from it every time.
"""

import gzip
import json

import pytest

import devtrace
import run as R
from run import BENCH

FIXTURE = BENCH / "fixtures" / "trace_gpt2s_1chip_sync.json.gz"
CELL = "gpt2s-1chip-h50"

# What the readers gave from the fixture when it was recorded.
EXPECTED = {
    "data_wait_ms": 1.8563403333333335,
    "device_idle_share": 20.79189137199299,
    "inner_step_device_ms": 133.89689866666666,
    "outer_step_device_ms": 13.673963,
    "flash_attention_fwd_roofline": 3.5252723920565256,
    "outer_step_roofline": 27.163681985799027,
    "mfu": 18.99518115537427,
}


def context():
    kept = json.load(gzip.open(FIXTURE, "rt"))
    red = devtrace.reduce(kept)
    cell = R.load_cell(CELL)
    # the fixture was recorded with the table padded to 50304 rows and 4096
    # positions; the counts follow the model the trace ran
    cell.config = dict(cell.config, vocab_size=50304,
                       max_position_embeddings=4096)
    waits = [h[2] * 1e-9 for h in kept["host"]
             if h[0] == "bench.next_batch"]
    steps = len(waits)
    tokens = steps * cell.traffic["global_batch"] * cell.traffic["seq_len"]
    return {"cell": cell, "window": {"data_waits": waits},
            "tokens_per_s": tokens / red.window_s, "chips": 1,
            "device_kind": "TPU v5 lite", "trace": red,
            "log": lambda _msg: None}


def readings():
    ctx = context()
    return {m["name"]: R._load_reader(m["name"])(ctx)
            for m in R.load_cell(CELL).per_layer}


def test_reduction_is_repeatable():
    assert readings() == readings()


def test_readings_as_recorded():
    got = readings()
    assert set(got) == set(EXPECTED)
    for name, want in EXPECTED.items():
        if want is None:
            assert got[name] is None, name
        else:
            assert got[name] == pytest.approx(want, rel=1e-9), name


def test_fixture_is_small_and_holds_a_sync():
    assert FIXTURE.stat().st_size < 1 << 20
    red = devtrace.reduce(json.load(gzip.open(FIXTURE, "rt")))
    assert red.modules("jit_outer_fn")[1] == 1
    assert red.modules("jit_stepfn")[1] == 3
    assert 0 < red.busy_s < red.window_s


def test_shares_are_shares():
    got = readings()
    for name in ("device_idle_share", "flash_attention_fwd_roofline",
                 "outer_step_roofline", "mfu"):
        assert 0 < got[name] <= 100, (name, got[name])
