"""The program-scope readers on a trimmed copy of a real chip trace.

``bench/fixtures/trace_gpt2s_1chip_scoped.json.gz`` is the kept form
(``progtrace.load``) of a ``--trace 1`` run of ``gpt2s-1chip-h50`` on one
TPU v5 lite with the program's scopes and spans, cut to three steps of the
window, the last of which runs the outer sync. The readers of the scopes
and spans must give the same numbers from it every time; a trace of a
program without them gives no number.
"""

import gzip
import json

import pytest

import devtrace
import progtrace as PT
import run as R
from run import BENCH

FIXTURE = BENCH / "fixtures" / "trace_gpt2s_1chip_scoped.json.gz"
DEVICE = ("blocks_fwd_device_ms", "blocks_bwd_device_ms", "head_device_ms",
          "inner_optimizer_device_ms")
GAPS = ("dispatch_gap_ms", "sync_gap_ms")
NEW = DEVICE + GAPS + ("outer_exchange_device_ms",)

# What the readers gave from the fixture when it was recorded.
EXPECTED = {
    "blocks_fwd_device_ms": 31.420982,
    "blocks_bwd_device_ms": 79.504949,
    "head_device_ms": 13.774423,
    "inner_optimizer_device_ms": 3.4272753333333337,
    "dispatch_gap_ms": 0.6947953333333334,
    "sync_gap_ms": 0.00025000000000000006,
    "outer_exchange_device_ms": None,  # one group: no exchange to time
}


def kept():
    return json.load(gzip.open(FIXTURE, "rt"))


def context(k=None):
    k = kept() if k is None else k
    win = PT.cut(k, *PT._bench_window(k))
    return {"program_trace": win, "log": lambda _msg: None}


def readings(ctx=None):
    ctx = context() if ctx is None else ctx
    return {name: R._load_reader(name)(ctx) for name in NEW}


def test_readings_as_recorded():
    got = readings()
    assert set(got) == set(EXPECTED)
    for name, want in EXPECTED.items():
        if want is None:
            assert got[name] is None, name
        else:
            assert got[name] == pytest.approx(want, rel=1e-9), name


def test_layers_add_up_to_the_inner_step():
    """Forward, backward, head and optimizer cover the inner step's
    module time to 5 %, and under 5 % of its op time has no scope."""
    win = context()["program_trace"]
    got = readings()
    runs = win.module_runs("jit_stepfn")
    module_ms = 1e3 * sum(e - s for d in win.devices[:1]
                          for n, s, e in d["modules"]
                          if n.startswith("jit_stepfn")) * 1e-9 / runs
    assert sum(got[n] for n in DEVICE) == pytest.approx(module_ms, rel=0.05)
    every = win.scoped_s("jit_stepfn", lambda s, b: True)
    unscoped = win.scoped_s("jit_stepfn", lambda s, b: s is None)
    assert unscoped <= 0.05 * every


def test_program_spans_hold_the_step_idle_time():
    split = context()["program_trace"].idle_by_span("bench.train_step")
    total = sum(split.values())
    assert total > 0
    assert total - split.get(PT.OUTSIDE, 0) >= 0.8 * total


def test_gaps_are_read():
    got = readings()
    for name in GAPS:
        assert got[name] is not None and got[name] >= 0, name


def test_fixture_is_small_and_holds_a_sync():
    assert FIXTURE.stat().st_size < 1 << 20
    win = context()["program_trace"]
    assert win.module_runs("jit_outer_fn") == 1
    assert win.module_runs("jit_stepfn") == 3
    assert len(win.named("trainer.outer")) == 1
    assert len(win.named("trainer.dispatch")) == 3


def test_a_program_without_scopes_or_spans_reads_nothing():
    """The parent program's trace: no scope paths, no program spans."""
    k = kept()
    bare = {"devices": [dict(d, ops=[o[:3] + [""] for o in d["ops"]])
                        for d in k["devices"]],
            "host": [h for h in k["host"] if h[0].startswith("bench.")]}
    assert all(v is None for v in readings(context(bare)).values())


@pytest.mark.parametrize("path,scope,backward", [
    ("jit(stepfn)/jit(main)/shard_map/jvp(pier.blocks)/dot_general",
     "pier.blocks", False),
    ("jit(stepfn)/shard_map/transpose(jvp(pier.blocks))/jit(_flash)/mul",
     "pier.blocks", True),
    ("jit(stepfn)/shard_map/transpose(jvp(pier.head))/reduce_sum",
     "pier.head", True),
    ("jit(stepfn)/shard_map/pier.optimizer/sqrt", "pier.optimizer", False),
    ("jit(outer_fn)/shard_map/pier.outer.update/pier.outer.delta/"
     "pier.outer.exchange/psum_invariant", "pier.outer.exchange", False),
    ("jit(stepfn)/transpose(jvp(pier.head))/dot_general:", "pier.head",
     True),  # as the chip's trace writes it
    ("jit(stepfn)/shard_map/convert_element_type", None, False),
    ("", None, False),
])
def test_scope_of(path, scope, backward):
    assert PT.scope_of(path) == (scope, backward)


def test_idle_is_put_down_to_the_innermost_span():
    win = PT.Window(
        start_ns=0, end_ns=100,
        devices=[{"ops": [["a", 0, 10, None, False],
                          ["b", 60, 100, None, False]], "modules": []}],
        spans=[["trainer.outer", 10, 50, 0], ["trainer.dispatch", 50, 60, 0],
               ["python.gc", 20, 30, 1], ["pipeline.make_batch", 10, 60, 1]],
        bench=[["bench.train_step", 5, 100]], main=0)
    assert win.idle_by_span() == pytest.approx(
        {"trainer.outer": 30e-9, "python.gc": 10e-9,
         "trainer.dispatch": 10e-9})
    assert win.idle_in("trainer.outer") == (pytest.approx(40e-9), 1)


def reduced(k):
    """``devtrace.reduce`` of the same trace, as a run hands it to the
    readers in ``ctx["trace"]``."""
    return devtrace.reduce({
        "devices": [{"ops": [o[:3] for o in d["ops"]],
                     "modules": d["modules"]} for d in k["devices"]],
        "host": [h[:3] for h in k["host"]]})


@pytest.mark.parametrize("shift_ns,found", [(0, True), (1, False)])
def test_window_reads_only_the_runs_own_trace(monkeypatch, shift_ns, found):
    """A trace under the trace directory whose ``bench.*`` spans bound
    another window than the run's is not read."""
    k = kept()
    monkeypatch.setattr(PT, "load", lambda _dir: k)
    red = reduced(k)
    red.start_ns += shift_ns
    got = readings({"trace": red, "log": lambda _msg: None})
    assert (got["head_device_ms"] is not None) == found


def test_window_without_a_trace_is_empty(monkeypatch, tmp_path):
    monkeypatch.setattr(PT, "TRACE_DIR", tmp_path)
    red = reduced(kept())
    assert all(v is None for v in readings(
        {"trace": red, "log": lambda _msg: None}).values())
