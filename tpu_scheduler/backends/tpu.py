"""TPU (JAX/XLA) batched backend — the ``--backend=tpu`` path.

Ships the packed tensors to device once per cycle and runs the whole
filter+score+commit auction under one jit (ops/assign.py).  Works on any JAX
platform (tests run it on CPU; the benchmark on a real v5e chip); the class
is named for its design target.
"""

from __future__ import annotations

import threading
import weakref

import numpy as np

from ..errors import BackendUnavailable
from ..models.profiles import SchedulingProfile
from ..ops.assign import assign_cycle, split_device_arrays
from ..ops.pack import PackedCluster
from ..utils.compile_cache import aot_call
from ..utils.profiler import install_jax_profile_hooks, record_transfer
from .base import SchedulingBackend

__all__ = ["TpuBackend"]


# shape: (assigned: [P] i32, acc_round: [P] i32, rank_of: [P] i32,
#   rounds: scalar i32) -> [4, P] i32
def _stack_results(assigned, acc_round, rank_of, rounds):
    """[4, P] i32: rows assigned / acc_round / rank_of / broadcast rounds —
    the single-fetch result layout (see _assign_once).  Module-level jit so
    the compiled stack is cached across cycles."""
    global _STACK_FN
    if _STACK_FN is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def stack(a, b, c, r):
            return jnp.stack([a, b, c, jnp.full_like(a, r)])

        _STACK_FN = stack
    return aot_call(_STACK_FN, {}, assigned, acc_round, rank_of, rounds)


_STACK_FN = None


class TpuBackend(SchedulingBackend):
    name = "tpu"
    supports_topology = True
    supports_exclusive_families = True

    def __init__(self, device=None, use_pallas: bool | None = None):
        try:
            import jax
        except Exception as e:  # pragma: no cover - jax is baked into the image
            raise BackendUnavailable(f"jax unavailable: {e}") from e
        self._jax = jax
        # Compile-vs-execute attribution: XLA compiles observed via
        # jax.monitoring land in the active cycle trace as ``compile`` spans
        # (best-effort, idempotent, never raises — utils/profiler.py).
        install_jax_profile_hooks()
        if device is None:
            devices = jax.devices()
            if not devices:
                raise BackendUnavailable("no jax devices")
            device = devices[0]
        self.device = device
        # The fused Pallas choose kernel (ops/pallas_choose.py) is
        # Mosaic/TPU-only; every other platform runs the jnp path (tests
        # exercise the kernel itself in interpreter mode).  A kernel that
        # fails to lower raises; nothing downgrades to the jnp path.
        self.use_pallas = (device.platform == "tpu") if use_pallas is None else use_pallas
        # Written only by shard_for (main-thread-only by routing.py's
        # contract); read from worker threads by _drop_dev_cache — the two
        # unlocked touches are pinned in scripts/analyze/baseline.json.
        self._shards: dict = {}  # guarded-by: _put_lock — device id -> shard backend (see shard_for)
        # Host→device upload cache: an unchanged pack (21 MB at flagship
        # scale) is uploaded once, not every cycle.  Keyed by host-array
        # identity (weakref-validated); safe because pack.py never mutates an
        # array it has handed out (repack_* replace, _grow_columns copies).
        # Locked: routed cycles call _assign_once from a thread pool on this
        # one instance.  Eviction is immediate via weakref.finalize — a dead
        # host array must release its device buffer within the cycle, not
        # after a size threshold (at flagship scale each stale pod pack pins
        # tens of MB of HBM).
        # Entry: (weakref, device_buf, finalizer).  The finalizer handle
        # lives IN the entry (not a separate id-keyed set): ids recycle the
        # moment an array dies, so a set would let a stale finalizer both
        # block registration for the id's new owner and — firing later —
        # leave the new owner's buffer pinned until _drop_dev_cache
        # (round-3 advisor finding).  Eviction compares the stored weakref
        # object itself, which is unambiguous across id reuse.
        #
        # Size-capped, oldest-insertion-first: on platforms where
        # device_put ALIASES the host buffer (CPU is zero-copy), the cached
        # device array keeps its host array alive, so weakref eviction
        # alone never fires and a long daemon's cache grows with every
        # repack (found by a 800-cycle churn soak).  A flagship cycle
        # touches a few dozen arrays; evicting a live entry is always safe
        # (worst case: one re-upload).
        self._dev_cache: dict[int, tuple[weakref.ref, object, object]] = {}  # guarded-by: _put_lock
        self._dev_cache_cap = 512
        self._put_lock = threading.Lock()
        # Fleet mesh-per-replica bindings (parallel/mesh.MeshBinding), keyed
        # by shard id.  Main-thread state: bound/released from the
        # controller's shard-refresh path only.
        self._mesh_bindings: dict[int, object] = {}

    # -- fleet mesh bindings (tpu_scheduler/fleet) --------------------------

    # shape: (self: obj, shard: int, num_shards: int) -> obj
    def bind_shard_mesh(self, shard: int, num_shards: int):
        """Bind one owned shard to this replica's contiguous device-slice
        mesh (parallel/mesh.mesh_binding).  Idempotent per (shard, K); a
        resize (new K) rebuilds the binding — the old slice geometry is
        meaningless under the new shard map."""
        ent = self._mesh_bindings.get(int(shard))
        if ent is not None and ent.num_shards == int(num_shards):
            return ent
        from ..parallel.mesh import mesh_binding

        ent = mesh_binding(int(shard), int(num_shards), devices=[self.device] if self.device else None)
        self._mesh_bindings[int(shard)] = ent
        return ent

    # shape: (self: obj, shard: int) -> bool
    def release_shard_mesh(self, shard: int) -> bool:
        """Forget a lost shard's binding (the new owner builds its own)."""
        return self._mesh_bindings.pop(int(shard), None) is not None

    # shape: (self: obj) -> obj
    def mesh_bindings_info(self) -> dict:
        """/debug/shards payload: per-shard device ids + mesh shape + the
        node-axis partition spec the slice's tensors are laid out over
        (parallel/mesh.node_sharding)."""
        from ..parallel.mesh import node_sharding

        return {
            str(s): {
                "devices": list(b.device_ids),
                "mesh_shape": [int(b.mesh.shape["dp"]), int(b.mesh.shape["tp"])],
                "dedicated": bool(b.dedicated),
                "node_sharding": str(node_sharding(b)),
            }
            for s, b in sorted(self._mesh_bindings.items())
        }

    def _drop_dev_cache(self) -> None:
        """Forget every cached upload — after a device-runtime failure the
        buffers may belong to a dead device session (device reset);
        recovery must re-upload, not reuse corpses.  A reset kills the
        whole session, so sibling per-device shard backends (shard_for)
        drop theirs too."""
        with self._put_lock:
            for ent in self._dev_cache.values():
                ent[2].detach()  # a re-upload registers a fresh finalizer
            self._dev_cache.clear()
        for sh in list(self._shards.values()):
            if sh is not self:
                sh._drop_dev_cache()

    def _evict(self, key: int, wr: weakref.ref) -> None:
        with self._put_lock:
            ent = self._dev_cache.get(key)
            # Drop only OUR entry: by the time a finalizer runs, the id may
            # already belong to a NEW cached array (CPython reuses ids) —
            # the stored weakref's identity disambiguates.
            if ent is not None and ent[0] is wr:
                del self._dev_cache[key]

    def _put(self, arr):
        """device_put with identity-keyed reuse of prior uploads."""
        key = id(arr)
        with self._put_lock:
            ent = self._dev_cache.get(key)
            if ent is not None and ent[0]() is arr:
                # Refresh recency (insertion order is the eviction order):
                # hot node tensors must outlive churned pod tensors.
                del self._dev_cache[key]
                self._dev_cache[key] = ent
                return ent[1]
        # Cache MISSES are real host->device traffic: count the bytes so the
        # profiler's compile/execute split (utils/profiler.py) can name
        # transfer-bound cycles (scheduler_device_transfer_bytes_total).
        nbytes = getattr(arr, "nbytes", None)
        if nbytes:
            record_transfer(int(nbytes))
        buf = self._jax.device_put(arr, self.device)
        try:
            wr = weakref.ref(arr)
        except TypeError:  # non-weakref-able input (e.g. a jax array): skip caching
            return buf
        fin = weakref.finalize(arr, self._evict, key, wr)
        fin.atexit = False  # interpreter teardown needs no cache hygiene
        with self._put_lock:
            old = self._dev_cache.pop(key, None)  # pop: the fresh entry must land at the MRU end
            if old is not None and old[0] is not wr:
                # The id's previous owner died (or this is a re-upload after
                # a cache drop): detach its finalizer so a late fire cannot
                # touch the new entry.
                old[2].detach()
            self._dev_cache[key] = (wr, buf, fin)
            while len(self._dev_cache) > self._dev_cache_cap:
                oldest = next(iter(self._dev_cache))
                if oldest == key:  # never evict the entry just inserted
                    break
                self._dev_cache.pop(oldest)[2].detach()
        return buf

    # shape: (packed: obj, profile: obj) -> ([P] i32, scalar i32, dict)
    # hotpath: tpu-solve
    def _assign_once(self, packed: PackedCluster, profile: SchedulingProfile):
        jax = self._jax
        a = packed.device_arrays()
        put = {k: self._put(v) for k, v in a.items()}
        weights = jax.device_put(profile.weights(), self.device)
        nodes, pods = split_device_arrays(put)
        cmeta = cstate = None
        cons = packed.constraints
        if cons is not None:
            pods.update({k: self._put(v) for k, v in cons.pod_arrays().items()})
            cmeta = {k: self._put(v) for k, v in cons.meta_arrays().items()}
            # Constraint STATE is mutated by the cycle only on device (the
            # loop carry); the host arrays are per-cycle fresh — still cheap
            # (domain-granular, "a rounding error" next to the pod tensors).
            cstate = {k: jax.device_put(v, self.device) for k, v in cons.state_arrays().items()}
        tmeta = tstate = None
        topo = packed.topology
        if topo is not None:
            # Topology tensors (topology/locality.py): the gang-id column
            # rides the pod dict (permuted/compacted/sliced with the rest);
            # meta is node/domain-side (cacheable uploads); the gang-count
            # STATE is loop-carried on device, per-cycle fresh like cstate.
            pods.update({k: self._put(v) for k, v in topo.pod_arrays().items()})
            tmeta = {k: self._put(v) for k, v in topo.meta_arrays().items()}
            tstate = {k: jax.device_put(v, self.device) for k, v in topo.state_arrays().items()}
        # The whole auction is one jit program — one host sync per cycle,
        # no jit-boundary relayouts — and its in-jit static size chain
        # (assign_cycle) shrinks the per-round cost with the active count.
        # It compiles ahead of time (aot_call): a program the compiler
        # refuses raises CompileFailed, outside the runtime-fault contract
        # in assign().
        static = {
            "max_rounds": profile.max_rounds,
            "block": profile.pod_block,
            "use_pallas": self.use_pallas,
            "soft_spread": cons is not None and cons.n_spread_soft > 0,
            "soft_pa": cons is not None and cons.n_ppa_terms > 0,
            "hard_pa": cons is not None and cons.n_pa_terms > 0,
        }
        operands = {"cmeta": cmeta, "cstate": cstate, "tmeta": tmeta, "tstate": tstate}
        out = aot_call(assign_cycle, static, nodes, pods, weights, **operands)
        assigned, rounds, _avail, acc_round, rank_of = out
        # ONE device→host fetch for the whole result: assigned/acc_round/
        # rank_of/rounds ride home stacked in a single [4, P] transfer
        # instead of four host syncs.
        combined = np.asarray(jax.device_get(_stack_results(assigned, acc_round, rank_of, rounds)))  # host-sync: the designed single [4, P] result fetch
        extras = {"acc_round": combined[1], "rank": combined[2]}
        return combined[0], int(combined[3, 0]), extras

    # shape: (packed: obj, profile: obj) -> ([P] i32, scalar i32, dict)
    def assign(self, packed: PackedCluster, profile: SchedulingProfile) -> tuple[np.ndarray, int]:
        try:
            return self._assign_once(packed, profile)
        except self._jax.errors.JaxRuntimeError as e:
            # Device-runtime failure (OOM, device lost, …) — the recovery
            # scenario the native fallback exists for (SURVEY.md §5).  Python
            # programming errors, kernel lowering errors and compile
            # failures (CompileFailed) propagate.
            self._drop_dev_cache()
            raise BackendUnavailable(f"tpu backend runtime failure: {e}") from e

    def shard_for(self, index: int) -> "TpuBackend":
        """Per-pool shard backend (parallel/routing.py): round-robin the pool
        shards over the visible device set so their solves overlap — the EP
        dispatch.  On one device every shard is this backend."""
        devices = self._jax.devices()
        if len(devices) <= 1:
            return self
        dev = devices[index % len(devices)]
        if dev == self.device:
            return self
        if dev.id not in self._shards:
            self._shards[dev.id] = TpuBackend(device=dev, use_pallas=self.use_pallas)
        return self._shards[dev.id]


# shape: (name: str) -> obj
def make_backend(name: str, **kw) -> SchedulingBackend:
    """Factory for the --backend flag."""
    from .native import NativeBackend

    if name == "native":
        return NativeBackend()
    if name == "tpu":
        return TpuBackend(**kw)
    raise ValueError(f"unknown backend {name!r} (expected 'native' or 'tpu')")
