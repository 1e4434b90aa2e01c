"""Quantized reduce-scatter + all-gather wire path (DESIGN.md §14).

The contract under test:

- ``reduce_scatter_qs`` delivers endpoint e the canonical-order mean of
  slot e of every source's quantized payload, bit-identical to
  ``reduce_scatter_qs_ref`` rows, across the ppermute-ring and one-hot
  psum transports, for int8 and nibble-packed int4, even/odd/pow2 E.
- The full rs → requantize(+residual2) → ag round trip reconstructs the
  identical payload on every endpoint, bit-for-bit against
  ``rs_ag_qs_ref``, and the second error-feedback residual telescopes
  exactly per slot: reduced + r2_in == dequant(q2, s2) + r2_out.
- Wire-shard edge cases (the satellite property tests): E not dividing
  the quant-block count (ragged last shard, zero-padded tail blocks are
  bit-transparent), int4 nibble packing at odd per-slot lengths, E=1.
- Measured per-device rs/ag bytes (real slot buffers) sit within 5% of
  the 2·(E−1)/E·payload model and ≤ 0.6× the all-reduce wire path's
  per-device sent bytes at E=4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import (dequant_concat_sources,
                               dequantize_blockwise_ref, pack_wire,
                               quantize_blockwise_ref, reduce_scatter_qs_ref,
                               rs_ag_qs_ref, shard_slot_wire,
                               wire_shard_blocks)
from repro.kernels.ring_allreduce import (allgather_qs, measure_wire_bytes,
                                          measured_rs_ag_bytes,
                                          reduce_scatter_qs)

BLOCK = 64


def _quantize_stack(x, bits, block=BLOCK):
    qs = [quantize_blockwise_ref(x[i], bits=bits, block=block)
          for i in range(x.shape[0])]
    return (jnp.stack([q for q, _ in qs]), jnp.stack([s for _, s in qs]))


# ---------------------------------------------------------------------------
# slot layout (shard_slot_wire)
# ---------------------------------------------------------------------------


def test_wire_shard_blocks_ceil_division():
    assert wire_shard_blocks(8, 4) == 2
    assert wire_shard_blocks(7, 3) == 3  # E does not divide nb
    assert wire_shard_blocks(1, 4) == 1
    assert wire_shard_blocks(5, 1) == 5
    with pytest.raises(ValueError):
        wire_shard_blocks(4, 0)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("nb,E", [(8, 4), (7, 3), (5, 4), (1, 2)])
def test_slot_padding_is_bit_transparent(bits, nb, E):
    """Zero-padded tail blocks carry zero scales and dequantize to exact
    zeros: concatenating all per-slot dequants reproduces the original
    dequantized payload followed by exact zeros."""
    n = nb * BLOCK - 3  # ragged tail inside the last real block too
    x = jax.random.normal(jax.random.PRNGKey(nb * E + bits), (n,),
                          jnp.float32)
    q, s = quantize_blockwise_ref(x, bits=bits, block=BLOCK)
    assert s.shape[0] == nb
    w_slots, s_slots = shard_slot_wire(q, s, bits=bits, block=BLOCK,
                                       endpoints=E)
    sb = wire_shard_blocks(nb, E)
    assert w_slots.shape[0] == E and s_slots.shape == (E, sb)
    full = np.asarray(dequant_concat_sources(w_slots, s_slots, bits=bits,
                                             block=BLOCK))
    ref = np.asarray(dequantize_blockwise_ref(q, s, block=BLOCK))
    np.testing.assert_array_equal(full[:ref.shape[0]], ref)
    np.testing.assert_array_equal(full[ref.shape[0]:],
                                  np.zeros(E * sb * BLOCK - ref.shape[0]))


def test_int4_nibbles_never_straddle_slots():
    """Per-slot packing at odd per-slot element counts: each slot packs
    independently (odd tail padded inside its own slot), so slot e of the
    wire buffer decodes without knowing its neighbors."""
    block, nb, E = 5, 7, 3  # sb=3 -> 15 elems/slot: odd, exercises the tail
    x = jax.random.normal(jax.random.PRNGKey(0), (nb * block,), jnp.float32)
    q, s = quantize_blockwise_ref(x, bits=4, block=block)
    w_slots, s_slots = shard_slot_wire(q, s, bits=4, block=block,
                                       endpoints=E)
    sb = wire_shard_blocks(nb, E)
    assert w_slots.shape == (E, (sb * block + 1) // 2)
    # independent decode of each slot == the padded payload's slots
    qp = jnp.pad(q, (0, (E * sb - nb) * block)).reshape(E, sb * block)
    for e in range(E):
        np.testing.assert_array_equal(
            np.asarray(w_slots[e]), np.asarray(pack_wire(qp[e], 4)))


# ---------------------------------------------------------------------------
# reduce_scatter_qs vs the reference oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("E", [2, 3, 4])  # even, odd, pow2
@pytest.mark.parametrize("transport", ["ring", "psum"])
def test_reduce_scatter_matches_ref_bitwise(bits, E, transport):
    n = 300  # 5 blocks of 64: E=3,4 do not divide nb — ragged last shard
    x = jax.random.normal(jax.random.PRNGKey(E + bits), (E, n), jnp.float32)
    q, s = _quantize_stack(x, bits)
    oracle = np.asarray(jax.jit(
        lambda q, s: reduce_scatter_qs_ref(q, s, block=BLOCK, bits=bits)
    )(q, s))

    def rs(qi, si):
        return reduce_scatter_qs(
            qi, si, axis_names=("x",), axis_sizes={"x": E}, bits=bits,
            block=BLOCK, transport=transport)

    got = jax.jit(jax.vmap(rs, axis_name="x"))(q, s)
    for e in range(E):  # endpoint e holds exactly oracle row e
        np.testing.assert_array_equal(np.asarray(got[e]), oracle[e])


def test_reduce_scatter_weighted_membership():
    """Elastic weights flow through the same dequant_sum_sources path."""
    E = 3
    x = jax.random.normal(jax.random.PRNGKey(9), (E, 256), jnp.float32)
    q, s = _quantize_stack(x, 8)
    w = jnp.array([1.0, 0.0, 1.0], jnp.float32)
    oracle = np.asarray(jax.jit(lambda q, s: reduce_scatter_qs_ref(
        q, s, block=BLOCK, bits=8, weights=w))(q, s))

    def rs(qi, si):
        return reduce_scatter_qs(
            qi, si, axis_names=("x",), axis_sizes={"x": E}, bits=8,
            block=BLOCK, transport="ring", weights=w)

    got = np.asarray(jax.jit(jax.vmap(rs, axis_name="x"))(q, s))
    for e in range(E):
        np.testing.assert_array_equal(got[e], oracle[e])


def test_reduce_scatter_multi_axis_linearizes_row_major():
    E1, E2 = 2, 3
    x = jax.random.normal(jax.random.PRNGKey(4), (E1 * E2, 256), jnp.float32)
    q, s = _quantize_stack(x, 8)
    oracle = np.asarray(jax.jit(
        lambda q, s: reduce_scatter_qs_ref(q, s, block=BLOCK))(q, s))

    for transport in ("ring", "psum"):
        def rs(qi, si, t=transport):
            return reduce_scatter_qs(
                qi, si, axis_names=("a", "b"),
                axis_sizes={"a": E1, "b": E2}, bits=8, block=BLOCK,
                transport=t)

        f = jax.vmap(jax.vmap(rs, axis_name="b"), axis_name="a")
        got = np.asarray(jax.jit(f)(q.reshape(E1, E2, -1),
                                    s.reshape(E1, E2, -1)))
        got = got.reshape(E1 * E2, -1)
        for e in range(E1 * E2):
            np.testing.assert_array_equal(got[e], oracle[e])


def test_rs_transports_agree_bitwise():
    E = 4
    x = jax.random.normal(jax.random.PRNGKey(2), (E, 320), jnp.float32)
    q, s = _quantize_stack(x, 4)
    outs = {}
    for transport in ("ring", "psum"):
        def rs(qi, si, t=transport):
            return reduce_scatter_qs(
                qi, si, axis_names=("x",), axis_sizes={"x": E}, bits=4,
                block=BLOCK, transport=t)
        outs[transport] = np.asarray(
            jax.jit(jax.vmap(rs, axis_name="x"))(q, s))
    np.testing.assert_array_equal(outs["ring"], outs["psum"])


# ---------------------------------------------------------------------------
# the full rs -> requantize -> ag round trip vs rs_ag_qs_ref
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("E", [2, 3, 4])
@pytest.mark.parametrize("transport", ["ring", "psum"])
def test_rs_ag_roundtrip_matches_ref_bitwise(bits, E, transport):
    nb = 5  # E=3,4 don't divide it; E=2 does with a ragged split at 3/2
    n = nb * BLOCK
    x = jax.random.normal(jax.random.PRNGKey(E * 7 + bits), (E, n),
                          jnp.float32)
    q, s = _quantize_stack(x, bits)
    sb = wire_shard_blocks(nb, E)
    r2 = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (E, sb * BLOCK))
    payload_ref, r2_ref = jax.jit(lambda q, s, r: rs_ag_qs_ref(
        q, s, block=BLOCK, bits=bits, residual2=r))(q, s, r2)

    def rs_ag(qi, si, r2i):
        shard = reduce_scatter_qs(
            qi, si, axis_names=("x",), axis_sizes={"x": E}, bits=bits,
            block=BLOCK, transport=transport)
        c2 = shard + r2i
        q2, s2 = quantize_blockwise_ref(c2, bits=bits, block=BLOCK)
        new_r2 = c2 - dequantize_blockwise_ref(q2, s2, block=BLOCK)
        payload = allgather_qs(
            q2, s2, axis_names=("x",), axis_sizes={"x": E}, bits=bits,
            block=BLOCK, transport=transport)
        return payload[:n], new_r2

    payload, new_r2 = jax.jit(jax.vmap(rs_ag, axis_name="x"))(q, s, r2)
    for e in range(E):  # identical payload bits on every endpoint
        np.testing.assert_array_equal(np.asarray(payload[e]),
                                      np.asarray(payload_ref))
        np.testing.assert_array_equal(np.asarray(new_r2[e]),
                                      np.asarray(r2_ref[e]))


def test_residual2_telescopes_exactly_per_slot():
    """reduced + r2_in == dequant(q2, s2) + r2_out, exactly: the gather
    leg's quantization error is carried, not lost."""
    E, nb = 3, 4
    n = nb * BLOCK
    x = jax.random.normal(jax.random.PRNGKey(11), (E, n))
    q, s = _quantize_stack(x, 8)
    sb = wire_shard_blocks(nb, E)
    # r2 zero in the slot-padding region (positions ≥ n): padded blocks
    # reduce to exact zeros, so a zero residual there stays zero — the
    # invariant the strategy's padded full-size residual2 buffer relies on.
    r2 = 0.05 * jax.random.normal(jax.random.PRNGKey(12), (E * sb * BLOCK,))
    r2 = r2.at[n:].set(0.0).reshape(E, sb * BLOCK)
    reduced = reduce_scatter_qs_ref(q, s, block=BLOCK, bits=8)
    payload, new_r2 = rs_ag_qs_ref(q, s, block=BLOCK, bits=8, residual2=r2)
    delivered = jnp.pad(payload, (0, E * sb * BLOCK - n)).reshape(
        E, sb * BLOCK)  # slot e as every endpoint sees it (pad dequants to 0)
    lhs = np.asarray(reduced + r2)
    rhs = np.asarray(delivered + new_r2)
    np.testing.assert_array_equal(lhs, rhs)


def test_rs_ag_single_endpoint_is_local_dequant():
    """E=1: the exchange degenerates to dequantize(quantize(shard))."""
    n = 2 * BLOCK
    x = jax.random.normal(jax.random.PRNGKey(3), (1, n))
    q, s = _quantize_stack(x, 8)
    payload, r2 = rs_ag_qs_ref(q, s, block=BLOCK, bits=8)
    # one slot == whole payload; the second quantization of an
    # already-on-grid payload is exact, so r2 stays zero
    local = dequantize_blockwise_ref(q[0], s[0], block=BLOCK)
    np.testing.assert_allclose(np.asarray(payload), np.asarray(local),
                               atol=1e-6)

    def rs(qi, si):
        return reduce_scatter_qs(qi, si, axis_names=("x",),
                                 axis_sizes={"x": 1}, bits=8, block=BLOCK,
                                 transport="ring")

    got = np.asarray(jax.jit(jax.vmap(rs, axis_name="x"))(q, s))
    np.testing.assert_array_equal(got[0], np.asarray(
        reduce_scatter_qs_ref(q, s, block=BLOCK, bits=8)[0]))


# ---------------------------------------------------------------------------
# measured bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_measured_rs_ag_bytes_within_5pct_of_model(bits):
    n, E = 1_000_000, 4
    m = measured_rs_ag_bytes(n, endpoints=E, bits=bits, block=256)
    per_elem = bits / 8.0 + 4.0 / 256
    model_per_device = 2.0 * (E - 1) / E * n * per_elem
    assert abs(m["measured_rs_ag_bytes_per_device"] / model_per_device
               - 1) < 0.05, m
    assert m["measured_rs_bytes_per_device"] == m["measured_ag_bytes_per_device"]
    assert m["measured_rs_ag_bytes_total"] == pytest.approx(
        E * m["measured_rs_ag_bytes_per_device"])


def test_rs_ag_beats_allreduce_wire_path_at_e4():
    """The acceptance bit: per-device sent bytes ≤ 0.6× the gather-based
    all-reduce wire path at E=4 (the true ratio is 2/E = 0.5)."""
    n, E = 1_000_000, 4
    rs_ag = measured_rs_ag_bytes(n, endpoints=E, bits=8, block=256)
    allreduce_sent = (E - 1) * measure_wire_bytes(
        n, bits=8, block=256)["measured_payload_bytes"]
    ratio = rs_ag["measured_rs_ag_bytes_per_device"] / allreduce_sent
    assert ratio <= 0.6, ratio
    assert ratio == pytest.approx(2.0 / E, rel=0.05)


# ---------------------------------------------------------------------------
# strategy resolution + composition rules
# ---------------------------------------------------------------------------

from repro.config import OuterCommConfig, ParallelConfig, TrainConfig  # noqa: E402
from repro.core.simulate import SimulatedRun  # noqa: E402
from repro.sync import (Chunked, FlatFP32, Hierarchical, Int8Wire,  # noqa: E402
                        MeasuredDelayController, Quantized, Sharded,
                        default_ladder, resolve_strategy)
from test_delayed_sync import MC  # noqa: E402


def _tc(**kw):
    base = dict(total_steps=40, global_batch_size=8, seq_len=16,
                sync_interval=5, inner_lr=1e-3, inner_min_lr=1e-4,
                warmup_frac=0.25)
    comm = kw.pop("comm", None)
    base.update(kw)
    tc = TrainConfig(**base)
    return tc.replace(outer_comm=comm) if comm is not None else tc


def test_rs_ag_resolution_and_names():
    tc = _tc(comm=OuterCommConfig(compression="rs-ag", bits=8, block=BLOCK))
    st = resolve_strategy(tc)
    assert isinstance(st, Int8Wire) and st.reduce_scatter
    assert st.name == f"rs-ag(int8,block={BLOCK})"
    assert st.wire_format == "int8+scales/rs-ag"
    assert st.needs_residual and st.needs_residual2
    plan = st.plan({"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)}, tc)
    assert plan.needs_residual2
    # the plain gather wire path keeps its names (and no second residual)
    plain = Int8Wire(bits=8, block=BLOCK)
    assert plain.name == f"int8-wire(block={BLOCK})"
    assert not plain.needs_residual2
    assert not plain.plan(
        {"w": jax.ShapeDtypeStruct((4,), jnp.float32)}, tc).needs_residual2
    # sharded + rs-ag resolves to Sharded(Int8Wire(reduce_scatter=True))
    sh = resolve_strategy(
        OuterCommConfig(compression="rs-ag", bits=4, block=BLOCK,
                        sharded=True))
    assert isinstance(sh, Sharded) and sh.inner.reduce_scatter
    assert sh.needs_residual2 and sh.wire_format == "int4+scales/rs-ag"


def test_rs_ag_combinator_exclusions():
    rs = Int8Wire(bits=8, block=BLOCK, reduce_scatter=True)
    with pytest.raises(ValueError, match="[Hh]ierarchical"):
        Hierarchical(inner=rs)
    with pytest.raises(ValueError, match="[Cc]hunked"):
        Chunked(inner=rs, num_chunks=2)
    with pytest.raises(ValueError, match="hierarchical"):
        OuterCommConfig(compression="rs-ag", hierarchical=True)
    with pytest.raises(ValueError, match="chunks"):
        OuterCommConfig(compression="rs-ag", chunks=2)
    # the plain wire path still composes with both combinators
    Hierarchical(inner=Int8Wire(bits=8, block=BLOCK))
    Chunked(inner=Int8Wire(bits=8, block=BLOCK), num_chunks=2)


def test_core_ladder_preserves_reduce_scatter():
    rs = Int8Wire(bits=8, block=BLOCK, reduce_scatter=True)
    ladder = default_ladder(rs)
    assert ladder[0] is rs
    assert ladder[1].bits == 4 and ladder[1].reduce_scatter
    assert ladder[1].block == BLOCK


# ---------------------------------------------------------------------------
# sim_reduce vs the shared reference oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_rs_ag_sim_reduce_matches_ref_bitwise(bits):
    tc = _tc(comm=OuterCommConfig(compression="rs-ag", bits=bits,
                                  block=BLOCK))
    st = resolve_strategy(tc)
    G, shape = 3, (10, 13)
    n = 130
    delta = {"w": jax.random.normal(jax.random.PRNGKey(1), (G, *shape))}
    r1 = {"w": 0.01 * jax.random.normal(jax.random.PRNGKey(2), (G, *shape))}
    r2 = {"w": jnp.zeros((G, *shape))}
    avg, (new_r1, new_r2) = jax.jit(
        lambda d, r: st.sim_reduce(d, r, tc, num_pods=1))(delta, (r1, r2))
    c = (delta["w"] + r1["w"]).reshape(G, -1)
    q, s = _quantize_stack(c, bits)
    sb = wire_shard_blocks(int(s.shape[1]), G)
    slot = sb * BLOCK
    payload, new_r2_shards = jax.jit(
        lambda q, s: rs_ag_qs_ref(q, s, block=BLOCK, bits=bits,
                                  residual2=jnp.zeros((G, slot))))(q, s)
    np.testing.assert_array_equal(
        np.asarray(avg["w"]), np.asarray(payload[:n].reshape(shape)))
    # first residual telescopes against the locally dequantized payload.
    # The wire payload above is bitwise; the residual subtraction c - q*s
    # may fuse differently under jit (FMA), so allow ~1 ulp here.
    local = jnp.stack([
        dequantize_blockwise_ref(q[g], s[g], block=BLOCK)[:n]
        for g in range(G)])
    np.testing.assert_allclose(
        np.asarray(new_r1["w"]), np.asarray((c - local).reshape(G, *shape)),
        atol=1e-6, rtol=0)
    # second residual: each group's row holds exactly its own slot
    got_r2 = np.asarray(new_r2["w"]).reshape(G, -1)
    for g in range(G):
        want = np.zeros(n, np.float32)
        lo, hi = g * slot, min((g + 1) * slot, n)
        want[lo:hi] = np.asarray(new_r2_shards)[g][:hi - lo]
        np.testing.assert_allclose(got_r2[g], want, atol=1e-6, rtol=0)


def test_rs_ag_sim_two_residuals_telescope_across_rounds():
    """Σ_rounds payload + mean(r1_T) + Σ_g r2_T[g] recovers Σ mean(Δθ):
    both error-feedback stages telescope instead of accumulating."""
    tc = _tc(comm=OuterCommConfig(compression="rs-ag", bits=8,
                                  block=BLOCK))
    st = resolve_strategy(tc)
    G, n = 3, 256
    key = jax.random.PRNGKey(5)
    res = ({"w": jnp.zeros((G, n))}, {"w": jnp.zeros((G, n))})
    total_wire = jnp.zeros((n,))
    total_true = jnp.zeros((n,))
    for _ in range(6):
        key, k = jax.random.split(key)
        delta = {"w": jax.random.normal(k, (G, n))}
        avg, res = st.sim_reduce(delta, res, tc, num_pods=1)
        total_wire = total_wire + avg["w"]
        total_true = total_true + jnp.mean(delta["w"], axis=0)
    r1, r2 = res
    recon = (total_wire + jnp.mean(r1["w"], axis=0)
             + jnp.sum(r2["w"], axis=0))
    np.testing.assert_allclose(np.asarray(recon), np.asarray(total_true),
                               atol=1e-5)


def test_rs_ag_sim_rejects_pod_grouping():
    st = Int8Wire(bits=8, block=BLOCK, reduce_scatter=True)
    with pytest.raises(ValueError, match="hierarchical"):
        st.sim_reduce({"w": jnp.zeros((4, 128))},
                      ({"w": jnp.zeros((4, 128))},
                       {"w": jnp.zeros((4, 128))}),
                      _tc(), num_pods=2, pod_grouped=True)


# ---------------------------------------------------------------------------
# Trainer vs simulator lockstep + convergence
# ---------------------------------------------------------------------------


def test_trainer_rs_ag_matches_simulator():
    tc = TrainConfig(optimizer="pier", total_steps=20, global_batch_size=4,
                     seq_len=16, sync_interval=4, warmup_frac=0.25, seed=0,
                     outer_comm=OuterCommConfig(
                         compression="rs-ag", bits=8, block=BLOCK))
    from repro.launch import mesh as M
    from repro.launch.train import Trainer

    sim = SimulatedRun(MC, tc, num_groups=1, seed=0)
    pc = ParallelConfig(data_axis_size=1, model_axis_size=1, data_outer=1)
    mesh = M.small_mesh((1, 1, 1), ("data_outer", "data_inner", "model"))
    tr = Trainer(MC, tc, pc, mesh)
    assert tr.bundle.plan.needs_residual2
    assert tr.outer.residual2 is not None
    for step in range(16):
        batch = sim._global_batch(step)
        tr.train_step(jax.device_put(batch, tr.bundle.batch_sharding(batch)))
        sim.run(1)
    worst = 0.0
    simp = (sim.state.group_params if sim.state.group_params is not None
            else sim.state.params)
    for a, b in zip(
            jax.tree.leaves(jax.tree.map(lambda g: g[0], simp)),
            jax.tree.leaves(jax.tree.map(lambda x: x[0], tr.state.params))):
        worst = max(worst, float(jnp.abs(jnp.asarray(a, jnp.float32)
                                         - jnp.asarray(b, jnp.float32)
                                         ).max()))
    assert worst < 5e-4, worst


def test_rs_ag_convergence_within_5pct_of_fp32():
    tc = _tc(total_steps=60, warmup_frac=0.2, sync_interval=5)
    eager = SimulatedRun(MC, tc, num_groups=2, seed=0)
    he = eager.run(60, eval_every=60)
    tw = _tc(total_steps=60, warmup_frac=0.2, sync_interval=5,
             comm=OuterCommConfig(compression="rs-ag", bits=8,
                                  block=BLOCK))
    wire = SimulatedRun(MC, tw, num_groups=2, seed=0)
    hw = wire.run(60, eval_every=60)
    ve, vw = he["val_loss"][-1], hw["val_loss"][-1]
    assert vw <= ve * 1.05, (ve, vw)


# ---------------------------------------------------------------------------
# warmup-sample width scaling (satellite: MeasuredDelayController)
# ---------------------------------------------------------------------------


def test_wire_bytes_per_param_model():
    tc = _tc()
    assert FlatFP32().wire_bytes_per_param(tc) == 4.0
    # Quantized's actual collective is the fp32 pmean of the dequantized
    # payload — full width on the wire
    assert Quantized(8, BLOCK).wire_bytes_per_param(tc) == 4.0
    w = Int8Wire(bits=8, block=BLOCK)
    assert w.wire_bytes_per_param(tc) == 8 / 8 + 4 / BLOCK
    assert Int8Wire(bits=4, block=BLOCK).wire_bytes_per_param(tc) == \
        4 / 8 + 4 / BLOCK
    # combinators delegate to the wire core
    assert Sharded(inner=w).wire_bytes_per_param(tc) == \
        w.wire_bytes_per_param(tc)
    assert Hierarchical(inner=w).wire_bytes_per_param(tc) == \
        w.wire_bytes_per_param(tc)
    assert Chunked(inner=w, num_chunks=2).wire_bytes_per_param(tc) == \
        w.wire_bytes_per_param(tc)


def test_warmup_samples_rescaled_by_payload_width():
    """Warmup accumulate windows exchange fp32 whatever the strategy;
    with warmup_scale the rescaled samples resolve the compressed wire's
    d* before the first post-warmup sync."""
    from repro.sync import FixedDelayController

    tc = _tc(sync_delay=0, sync_interval=10)
    scale = Int8Wire(bits=8, block=BLOCK).wire_bytes_per_param(tc) / 4.0
    c = MeasuredDelayController(tc, fallback=FixedDelayController(9),
                                min_windows=2, skip_windows=1,
                                warmup_scale=scale)
    c.observe_step(0.1)
    for _ in range(3):  # 1 skip + 2 measured warmup windows
        c.observe_window(t_comm=0.8, warmup=True)
    # fp32 sample 0.8s -> int8 wire estimate 0.8*scale ~ 0.2125s -> d*=3
    assert c.current_delay() == int(np.ceil(0.8 * scale / 0.1))
    # without the warmup flag the sample enters the EMA unscaled
    c2 = MeasuredDelayController(tc, fallback=FixedDelayController(9),
                                 min_windows=2, skip_windows=1,
                                 warmup_scale=scale)
    c2.observe_step(0.1)
    for _ in range(3):
        c2.observe_window(t_comm=0.8)
    assert c2.current_delay() == 8  # ceil(0.8/0.1)


def test_strategy_warmup_scale_reaches_controller():
    tc = _tc(sync_delay=0)
    w = Int8Wire(bits=8, block=BLOCK)
    ctrl = w.make_delay_controller(tc, None, None)
    assert isinstance(ctrl, MeasuredDelayController)
    assert ctrl.warmup_scale == pytest.approx(
        w.wire_bytes_per_param(tc) / 4.0)
    # fp32 strategies keep warmup samples exact
    assert FlatFP32().make_delay_controller(
        tc, None, None).warmup_scale == 1.0


# ---------------------------------------------------------------------------
# ragged sharded leaves
# ---------------------------------------------------------------------------


def test_sharded_quantized_ragged_leaf_pads_shard_locally():
    """Sharded(Quantized) ragged leaves pad to whole blocks in-graph and
    quantize the same blocks as the unsharded Quantized round trip, with
    the exact error-feedback identity c == payload + residual'."""
    from repro.sync import ReduceCtx

    ctx = ReduceCtx(manual=(), fast_axes=(), slow_axes=(),
                    exchange_axes=(), axis_sizes={})
    n = BLOCK * 2 + 7  # ragged: does not divide block * auto_size
    d = jax.random.normal(jax.random.PRNGKey(11), (n,))
    r = 0.01 * jax.random.normal(jax.random.PRNGKey(12), (n,))
    tc = _tc()

    payload, new_r = Sharded(inner=Quantized(8, BLOCK)).reduce_leaf(
        d, r, tc, ctx)
    assert payload.shape == (n,) and new_r.shape == (n,)
    np.testing.assert_allclose(
        np.asarray(payload + new_r), np.asarray(d + r), atol=1e-6)
    ref_payload, ref_r = Quantized(8, BLOCK).reduce_leaf(d, r, tc, ctx)
    np.testing.assert_allclose(np.asarray(payload), np.asarray(ref_payload),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_r), np.asarray(ref_r),
                               atol=1e-6)
