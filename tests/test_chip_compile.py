"""Ahead-of-time compiles of the main path for a described (not attached)
TPU v5e: the fused choose kernel in both variants at flagship widths, the
monolithic ``assign_cycle`` with the kernel, and the 2×2 shard_map program.
What the chip's compiler refuses fails here, at no chip time.

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and every xdist worker imports this
file.  Keep these compiles in this one file (see the on-chip-measurement
guide, section 2).
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding  # noqa: E402

# Widths of the 100k x 10k ``--workload mixed`` pack (cli.py fractions,
# seed 0): selector/taint/node-affinity/preferred/soft-taint vocabularies
# and the anti-affinity carrier (Tc), hard spread (S), positive affinity
# (Ta), soft spread (Ss) and preferred inter-pod (Tp) term widths.
L, T, A, A2, TS, R = 8, 8, 40, 8, 8, 3
TC, S, TA, SS, TP = 56, 104, 8, 104, 8
B, N = 8192, 10112  # throughput profile pod block x padded flagship nodes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's compiles can be written to the persistent cache
    # but never read back without the chip; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits_v5e(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30, f"program needs {used} bytes of a 16 GB v5e"


@pytest.mark.parametrize("constrained", [False, True], ids=["plain", "constrained"])
def test_choose_kernel_compiles_for_v5e(one_chip, constrained):
    from tpu_scheduler.ops.pallas_choose import KERNEL_NAMES, choose_block_pallas

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        s((B, R), jnp.int32), s((B, L)), s((B,)), s((B, T)), s((B, A)), s((B,)), s((B, A2)), s((B, TS)),
        s((B,), jnp.bool_), s((B,), jnp.uint32), s((8, N), jnp.int32), s((L, N)), s((T, N)), s((A, N)),
        s((A2, N)), s((TS, N)), s((8,)),
    )
    kw = {}
    if constrained:
        kw["cons_pod"] = tuple(s((B, w)) for w in (TC, TC, S, TA, SS, S, TP))
        kw["cons_node"] = tuple(s((w, N)) for w in (TC, TC, S, TA, SS, S, TP))
    compiled = choose_block_pallas.lower(*args, salt=s((), jnp.int32), **kw).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{KERNEL_NAMES[constrained]}." in text  # the name chip_smoke.py finds in the device trace
    _fits_v5e(compiled)


@pytest.fixture(scope="module")
def packed():
    from tpu_scheduler.ops.pack import pack_snapshot
    from tpu_scheduler.testing import synth_cluster

    # Flagship node axis; a short pod axis keeps the size chain (and the
    # compile) short — the kernel itself is compiled at B above.
    return pack_snapshot(synth_cluster(n_nodes=10_000, n_pending=12_000, n_bound=20_000, seed=0), node_block=128)


def test_assign_cycle_with_kernel_compiles_for_v5e(one_chip, packed):
    from tpu_scheduler.models.profiles import PROFILES
    from tpu_scheduler.ops.assign import assign_cycle, split_device_arrays

    prof = PROFILES["throughput"]
    spec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip) for k, v in packed.device_arrays().items()}
    nodes, pods = split_device_arrays(spec)
    w = prof.weights()
    weights = jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=one_chip)
    compiled = assign_cycle.lower(
        nodes, pods, weights, max_rounds=prof.max_rounds, block=prof.pod_block, use_pallas=True
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_v5e(compiled)


def test_shard_map_program_compiles_on_2x2_mesh(topo, packed):
    from tpu_scheduler.models.profiles import PROFILES
    from tpu_scheduler.parallel.mesh import make_mesh
    from tpu_scheduler.parallel.sharded import _build_sharded_fn

    prof = PROFILES["throughput"]
    mesh = make_mesh(topo.devices, tp=2)
    assert dict(mesh.shape) == {"dp": 2, "tp": 2}
    rep = NamedSharding(mesh, PartitionSpec())
    arrays = dict(packed.device_arrays(), weights=np.asarray(prof.weights(), np.float32))
    spec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=rep) for k, v in arrays.items()}
    run = _build_sharded_fn(mesh, prof.max_rounds, use_pallas=True)
    text = run.lower(spec, {}).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text  # the cross-shard merges are real collectives


def test_jobset_fleet_cycle_compiles_for_v5e(one_chip):
    """The TPU-slice JobSet fleet at its full size (472 pools, 4,096 hosts,
    a wave of 96 jobs against 354 running): exclusive families plus the gang
    locality term, so the jnp choose tree and no kernel."""
    from dataclasses import replace

    from tpu_scheduler.models.profiles import DEFAULT_PROFILE
    from tpu_scheduler.ops.assign import assign_cycle, split_device_arrays
    from tpu_scheduler.ops.constraints import pack_constraints
    from tpu_scheduler.ops.pack import pack_snapshot, pad_pods
    from tpu_scheduler.testing import jobset_fleet
    from tpu_scheduler.topology.locality import pack_topology
    from tpu_scheduler.topology.model import TopologyModel

    snap = jobset_fleet({"4x4": 256, "4x8": 128, "8x8": 64, "8x16": 16, "16x16": 8}, 0.75, 96, seed=0)
    packed = pack_snapshot(snap)
    cons = pack_constraints(snap, snap.pending_pods(), packed.padded_pods, packed.node_names, packed.padded_nodes)
    assert cons.n_families == 1 and cons.n_terms == 0 and cons.n_pa_terms == 0
    packed = replace(pad_pods(packed, cons.padded_pods), constraints=cons)
    topo = pack_topology(TopologyModel.detect(snap.nodes).compile(snap.nodes), snap.pending_pods(),
                         packed.padded_pods, packed.node_names, packed.padded_nodes)

    def s(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)

    nodes, pods = split_device_arrays({k: s(v) for k, v in packed.device_arrays().items()})
    pods.update({k: s(v) for k, v in {**cons.pod_arrays(), **topo.pod_arrays()}.items()})
    w = DEFAULT_PROFILE.weights()
    compiled = assign_cycle.lower(
        nodes, pods, jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=one_chip),
        max_rounds=DEFAULT_PROFILE.max_rounds, block=DEFAULT_PROFILE.pod_block, use_pallas=True,
        cmeta={k: s(v) for k, v in cons.meta_arrays().items()}, cstate={k: s(v) for k, v in cons.state_arrays().items()},
        hard_pa=False, tmeta={k: s(v) for k, v in topo.meta_arrays().items()},
        tstate={k: s(v) for k, v in topo.state_arrays().items()},
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    _fits_v5e(compiled)
