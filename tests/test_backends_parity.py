"""Binding parity: the native (NumPy) and TPU (JAX) backends must produce
*identical* assignments — the north-star parity oracle (BASELINE.md), made
exact by sharing the mask/score expression trees and mirroring the commit
arithmetic (saturating scan ≡ int64+clamp).

Runs JAX on the virtual 8-device CPU platform (tests/conftest.py); the same
jitted code path runs on a real TPU in chip_smoke.py and the benchmark.
"""

import numpy as np
import pytest

from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.backends.tpu import TpuBackend, make_backend
from tpu_scheduler.models.profiles import DEFAULT_PROFILE, PROFILES
from tpu_scheduler.ops.pack import pack_snapshot
from tpu_scheduler.testing import synth_cluster

from test_assign import check_validity


@pytest.fixture(scope="module")
def tpu_backend():
    return TpuBackend()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize(
    "shape",
    [(5, 10), (16, 100), (64, 500)],
)
def test_backend_parity(tpu_backend, seed, shape):
    n_nodes, n_pending = shape
    snap = synth_cluster(n_nodes=n_nodes, n_pending=n_pending, n_bound=n_nodes, seed=seed)
    packed = pack_snapshot(snap)
    native = NativeBackend().schedule(packed)
    tpu = tpu_backend.schedule(packed)
    assert (native.assigned == tpu.assigned).all(), (
        f"parity violation at seed={seed} shape={shape}: "
        f"{np.flatnonzero(native.assigned != tpu.assigned)[:10]}"
    )
    assert native.rounds == tpu.rounds
    check_validity(snap, packed, tpu)


def test_parity_under_contention(tpu_backend):
    # Demand ≈ 3× capacity: heavy per-node contention, many auction rounds.
    snap = synth_cluster(n_nodes=8, n_pending=400, seed=11, selector_fraction=0.3)
    packed = pack_snapshot(snap)
    profile = DEFAULT_PROFILE.with_(max_rounds=256)
    native = NativeBackend().schedule(packed, profile)
    tpu = tpu_backend.schedule(packed, profile)
    assert (native.assigned == tpu.assigned).all()
    check_validity(snap, packed, tpu)


@pytest.mark.parametrize("profile_name", sorted(PROFILES))
def test_parity_across_profiles(tpu_backend, profile_name):
    snap = synth_cluster(n_nodes=24, n_pending=200, n_bound=48, seed=5)
    packed = pack_snapshot(snap)
    profile = PROFILES[profile_name]
    native = NativeBackend().schedule(packed, profile)
    tpu = tpu_backend.schedule(packed, profile)
    assert (native.assigned == tpu.assigned).all()


def test_blockwise_choose_matches_single_shot(tpu_backend):
    # pod_block smaller than P exercises the lax.map blockwise path.
    snap = synth_cluster(n_nodes=16, n_pending=300, seed=9)
    packed = pack_snapshot(snap, pod_block=128)
    small = tpu_backend.schedule(packed, DEFAULT_PROFILE.with_(pod_block=128))
    big = tpu_backend.schedule(packed, DEFAULT_PROFILE.with_(pod_block=1 << 20))
    assert (small.assigned == big.assigned).all()


def test_make_backend_factory():
    assert make_backend("native").name == "native"
    assert make_backend("tpu").name == "tpu"
    with pytest.raises(ValueError):
        make_backend("cuda")


def test_throughput_profile_converges_faster(tpu_backend):
    """The mass-admission profile's wide jitter must cut auction rounds on a
    contended cluster — with native/tpu parity intact and identical validity."""
    snap = synth_cluster(n_nodes=64, n_pending=1500, n_bound=128, seed=3)
    packed = pack_snapshot(snap)
    deft = PROFILES["default"].with_(max_rounds=64)
    thr = PROFILES["throughput"].with_(max_rounds=64)
    r_def = NativeBackend().schedule(packed, deft)
    r_thr_n = NativeBackend().schedule(packed, thr)
    r_thr_t = tpu_backend.schedule(packed, thr)
    assert r_thr_n.bindings == r_thr_t.bindings  # parity under the new profile
    assert len(r_thr_n.bindings) == len(r_def.bindings)  # same admission
    assert r_thr_n.rounds < r_def.rounds  # and fewer rounds
    check_validity(snap, packed, r_thr_t)


def test_upload_cache_reuses_and_evicts():
    """The host→device upload cache must serve repeat schedules of the same
    pack without stale results, and release device buffers as soon as the
    host arrays die (review: a size-thresholded eviction pinned HBM for ~25
    cycles at flagship scale)."""
    import gc

    from tpu_scheduler.backends.tpu import TpuBackend

    b = TpuBackend()
    packed = pack_snapshot(synth_cluster(n_nodes=20, n_pending=100, n_bound=10, seed=3))
    r1 = b.schedule(packed)
    r2 = b.schedule(packed)  # second pass rides the cache
    assert (r1.assigned == r2.assigned).all()
    assert len(b._dev_cache) > 0
    n_before = len(b._dev_cache)
    del packed, r1, r2
    gc.collect()
    # Some arrays may legitimately outlive the pack (module-level template
    # caches); the contract is: no DEAD entry may keep its device buffer.
    assert len(b._dev_cache) < n_before, "dropping the pack must evict buffers"
    assert all(r() is not None for r, _, _f in b._dev_cache.values()), "dead entries must be evicted immediately"
