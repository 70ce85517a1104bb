"""Multi-chip sharding tests on the 8-virtual-device CPU mesh.

conftest.py forces JAX_PLATFORMS=cpu with
--xla_force_host_platform_device_count=8 before jax is imported, so these
run without TPU hardware; the same code paths drive real chips.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from racon_tpu.ops.align import nw_align_batch, nw_scores
from racon_tpu.parallel.dispatch import (make_mesh, nw_align_batch_sharded,
                                         sp_nw_align, sp_nw_scores)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(5)
    B, Lq, Lt = 6, 40, 64
    q = np.zeros((B, Lq), np.uint8)
    t = np.zeros((B, Lt), np.uint8)
    lq = rng.integers(5, Lq, B).astype(np.int32)
    lt = rng.integers(8, Lt, B).astype(np.int32)
    for b in range(B):
        q[b, :lq[b]] = rng.integers(0, 4, lq[b])
        t[b, :lt[b]] = rng.integers(0, 4, lt[b])
    return q, t, lq, lt


def test_eight_cpu_devices_present():
    assert len(jax.devices()) >= 8
    assert all(d.platform == "cpu" for d in jax.devices())


def test_dp_sharded_align_equals_single_device(batch):
    q, t, lq, lt = batch
    mesh = make_mesh(8, axes=("dp",))
    ops_s, n_s = nw_align_batch_sharded(mesh, q, t, lq, lt,
                                        match=5, mismatch=-4, gap=-8)
    ops_r, n_r = nw_align_batch(jnp.asarray(q), jnp.asarray(t),
                                jnp.asarray(lq), jnp.asarray(lt),
                                match=5, mismatch=-4, gap=-8)
    assert np.array_equal(np.asarray(n_r), n_s)
    assert np.array_equal(np.asarray(ops_r), ops_s)


def test_sp_sequence_parallel_scores_equal_single_device(batch):
    q, t, lq, lt = batch
    mesh = make_mesh(8, axes=("dp", "sp"))
    assert mesh.shape["sp"] > 1  # genuinely sharded target axis
    sc_sp = sp_nw_scores(mesh, q, t, lq, lt, match=5, mismatch=-4, gap=-8)
    sc_r = np.asarray(nw_scores(jnp.asarray(q), jnp.asarray(t),
                                jnp.asarray(lq), jnp.asarray(lt),
                                match=5, mismatch=-4, gap=-8))
    assert np.array_equal(sc_r, sc_sp)


def test_sp_sequence_parallel_align_matches_single_device(batch):
    """Full sp traceback (a round-3 review finding): the target-sharded forward +
    replicated psum walk must reproduce the single-device alignment
    bit-for-bit (same DP values, same DIAG>UP>LEFT tie rule)."""
    q, t, lq, lt = batch
    mesh = make_mesh(8, axes=("dp", "sp"))
    assert mesh.shape["sp"] > 1
    ops_s, n_s = sp_nw_align(mesh, q, t, lq, lt,
                             match=5, mismatch=-4, gap=-8)
    ops_r, n_r = nw_align_batch(jnp.asarray(q), jnp.asarray(t),
                                jnp.asarray(lq), jnp.asarray(lt),
                                match=5, mismatch=-4, gap=-8)
    assert np.array_equal(np.asarray(n_r), n_s)
    assert np.array_equal(np.asarray(ops_r), ops_s)


def test_engine_with_mesh_matches_engine_without():
    from racon_tpu.models.window import Window, WindowType
    from racon_tpu.ops.encode import decode_bases
    from racon_tpu.ops.poa import PoaEngine

    rng = np.random.default_rng(6)
    true = rng.integers(0, 4, 120).astype(np.uint8)
    backbone = decode_bases(true)

    def build():
        w = Window(0, 0, WindowType.TGS, backbone, None)
        for k in range(5):
            lay = bytearray(backbone)
            lay[10 + k] = ord("T") if lay[10 + k] != ord("T") else ord("A")
            w.add_layer(bytes(lay), None, 0, len(backbone) - 1)
        return w

    w_single = build()
    w_mesh = build()
    PoaEngine(backend="jax").consensus_windows([w_single])
    PoaEngine(backend="jax",
              mesh=make_mesh(8, axes=("dp",))).consensus_windows([w_mesh])
    assert w_single.consensus == w_mesh.consensus


def test_sharded_device_engine_noisy_windows():
    """The dp-sharded chunk program on the 8-device mesh must reproduce the
    single-device engine bit-for-bit on realistic noisy windows (psum'd
    vote accumulators, jobs of one window spread across shards)."""
    from synth_windows import build_windows
    from racon_tpu.ops.poa import PoaEngine

    ws_ref = build_windows(10, 6, 130, seed=11)
    ws_dp = build_windows(10, 6, 130, seed=11)
    assert PoaEngine(backend="jax").consensus_windows(ws_ref) == 10
    mesh = make_mesh(8, axes=("dp",))
    assert PoaEngine(backend="jax",
                     mesh=mesh).consensus_windows(ws_dp) == 10
    # The psum reassociates f32 vote sums vs the unsharded matmul, so a
    # sub-ulp tie can legitimately flip a near-tied column; require
    # near-total agreement rather than strict bit equality.
    same = sum(a.consensus == b.consensus for a, b in zip(ws_ref, ws_dp))
    assert same >= 9, f"only {same}/10 windows identical"


def test_sp_routing_for_over_budget_windows():
    """A window whose alignment jobs exceed the single-chip dirs budget
    must route through the sequence-parallel NW when the mesh has an
    "sp" axis, and produce a consensus bit-equal to the pure host path
    (sp was test-only plumbing before round 4)."""
    from racon_tpu.models.window import Window, WindowType
    from racon_tpu.ops.encode import decode_bases
    from racon_tpu.ops.poa import PoaEngine

    rng = np.random.default_rng(9)
    true = rng.integers(0, 4, 160).astype(np.uint8)
    backbone = decode_bases(true)

    def build():
        w = Window(0, 0, WindowType.TGS, backbone, None)
        for k in range(4):
            lay = bytearray(backbone)
            lay[30 + 3 * k] = ord("ACGT"[(true[30 + 3 * k] + 1) % 4])
            w.add_layer(bytes(lay), None, 0, len(backbone) - 1)
        return w

    w_host = build()
    w_sp = build()
    # Host reference (no mesh, native aligner).
    PoaEngine(backend="native").consensus_windows([w_host])
    # sp-routed: shrink the budget so these 160x160 jobs overflow it.
    eng = PoaEngine(backend="native", mesh=make_mesh(8, axes=("dp", "sp")))
    eng.sp_cell_budget = 10_000
    jobs_seen = []
    orig = eng._align_sp

    def spy(jobs):
        jobs_seen.extend(jobs)
        return orig(jobs)

    eng._align_sp = spy
    eng.consensus_windows([w_sp])
    assert jobs_seen, "no job routed through the sp aligner"
    assert w_host.consensus == w_sp.consensus


def test_graft_entry_single_chip():
    import __graft_entry__ as graft
    fn, args = graft.entry()
    out = jax.jit(fn).lower(*args).compile()(*args)
    assert np.asarray(out).shape == (64,)


# ---------------------------------------------- fault-site coverage

def test_h2d_align_fault_site_retried(batch):
    """The declared h2d/align site is live: a one-shot injected fault
    on the sharded upload is absorbed by one retry and the result stays
    bit-identical (lint rule FLT002 requires every declared site to be
    exercised)."""
    from racon_tpu.obs import metrics as obs_metrics
    from racon_tpu.resilience import faults, retry
    q, t, lq, lt = batch
    mesh = make_mesh(8, axes=("dp",))
    ops_r, n_r = nw_align_batch(jnp.asarray(q), jnp.asarray(t),
                                jnp.asarray(lq), jnp.asarray(lt),
                                match=5, mismatch=-4, gap=-8)
    retry.configure(retry.RetryPolicy(attempts=2, base=0.0, jitter=0.0))
    faults.configure("h2d/align:0")
    try:
        ops_s, n_s = nw_align_batch_sharded(mesh, q, t, lq, lt,
                                            match=5, mismatch=-4, gap=-8)
        snap = obs_metrics.registry().snapshot()
    finally:
        retry.configure(None)
        faults.configure(None)
        obs_metrics.reset()
    assert snap["res_fault_injected_total"] >= 1
    assert snap["res_retry_total"] >= 1
    assert np.array_equal(np.asarray(n_r), n_s)
    assert np.array_equal(np.asarray(ops_r), ops_s)


def test_d2h_sp_fault_site_retried(batch):
    """Same drill for the d2h/sp pull on the sequence-parallel path."""
    from racon_tpu.obs import metrics as obs_metrics
    from racon_tpu.resilience import faults, retry
    q, t, lq, lt = batch
    mesh = make_mesh(8, axes=("dp", "sp"))
    sc_r = np.asarray(nw_scores(jnp.asarray(q), jnp.asarray(t),
                                jnp.asarray(lq), jnp.asarray(lt),
                                match=5, mismatch=-4, gap=-8))
    retry.configure(retry.RetryPolicy(attempts=2, base=0.0, jitter=0.0))
    faults.configure("d2h/sp:0")
    try:
        sc_sp = sp_nw_scores(mesh, q, t, lq, lt,
                             match=5, mismatch=-4, gap=-8)
        snap = obs_metrics.registry().snapshot()
    finally:
        retry.configure(None)
        faults.configure(None)
        obs_metrics.reset()
    assert snap["res_fault_injected_total"] >= 1
    assert snap["res_retry_total"] >= 1
    assert np.array_equal(sc_r, sc_sp)
