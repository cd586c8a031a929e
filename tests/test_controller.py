"""Control-loop e2e against the fake API server: both policies, requeue
semantics, fallback, incremental repack, and the CLI."""

import json
import random
import os
import subprocess
import sys

from tpu_scheduler.backends.native import NativeBackend
from tpu_scheduler.errors import BackendUnavailable
from tpu_scheduler.runtime.controller import Scheduler
from tpu_scheduler.runtime.fake_api import FakeApiServer
from tpu_scheduler.testing import make_node, make_pod, synth_cluster


from conftest import FakeClock


def make_cluster_api(n_nodes=10, n_pending=40, seed=0, **kw):
    api = FakeApiServer()
    snap = synth_cluster(n_nodes=n_nodes, n_pending=n_pending, seed=seed, **kw)
    api.load(snap.nodes, snap.pods)
    return api


def test_batch_policy_binds_everything():
    api = make_cluster_api(10, 40)
    sched = Scheduler(api, NativeBackend())
    m = sched.run_cycle()
    assert m.bound == 40 and m.unschedulable == 0
    assert len(api.list_pods("status.phase=Pending")) == 0
    # Next cycle is a no-op (all bound).
    m2 = sched.run_cycle()
    assert m2.pending == 0 and m2.bound == 0


def test_incremental_repack_used_between_cycles():
    api = make_cluster_api(8, 30)
    sched = Scheduler(api, NativeBackend())
    sched.run_cycle()
    for i in range(5):
        api.create_pod(make_pod(f"late-{i}", cpu="100m", memory="128Mi"))
    m = sched.run_cycle()
    assert m.bound == 5
    counters = sched.metrics.snapshot()
    assert counters["scheduler_full_packs_total"] == 1
    assert counters["scheduler_incremental_packs_total"] == 1


def test_node_change_forces_full_pack():
    api = make_cluster_api(4, 10)
    sched = Scheduler(api, NativeBackend())
    sched.run_cycle()
    api.create_node(make_node("fresh-node", cpu="32", memory="128Gi"))
    api.create_pod(make_pod("late", cpu="1", memory="1Gi"))
    sched.run_cycle()
    assert sched.metrics.snapshot()["scheduler_full_packs_total"] == 2


def test_unschedulable_requeues_after_300s():
    clock = FakeClock()
    api = FakeApiServer()
    api.create_node(make_node("tiny", cpu="1", memory="1Gi"))
    api.create_pod(make_pod("huge", cpu="64", memory="256Gi"))
    # delta=False: this pins the BACKOFF contract (the reference's flat
    # error_policy retry).  With the delta engine on, a futile retry is
    # elided by the standing verdict instead — tests/test_delta.py pins that.
    sched = Scheduler(api, NativeBackend(), clock=clock, delta=False)
    m1 = sched.run_cycle()
    assert m1.unschedulable == 1
    # Still backing off: pod is not eligible.
    clock.t = 299.0
    assert sched.run_cycle().pending == 0
    # After the requeue window it is retried (and fails again, like the
    # reference's forever-requeue of never-fitting pods).
    clock.t = 301.0
    m3 = sched.run_cycle()
    assert m3.pending == 1 and m3.unschedulable == 1


def test_binding_failure_requeues_pod():
    clock = FakeClock()
    api = FakeApiServer()
    api.create_node(make_node("n1", cpu="8", memory="32Gi"))
    api.create_pod(make_pod("p1", cpu="1", memory="1Gi"))
    api.fail_next_bindings = 1
    sched = Scheduler(api, NativeBackend(), clock=clock)
    m1 = sched.run_cycle()
    assert m1.bound == 0
    assert sched.metrics.snapshot()["scheduler_requeues_total"] == 1
    clock.t = 301.0
    m2 = sched.run_cycle()
    assert m2.bound == 1
    assert len(api.list_pods("status.phase=Pending")) == 0


class ExplodingBackend(NativeBackend):
    name = "exploding"

    def assign(self, packed, profile):
        raise BackendUnavailable("injected device loss")


def test_fallback_to_native_on_backend_failure():
    api = make_cluster_api(6, 20)
    sched = Scheduler(api, ExplodingBackend(), fallback_backend=NativeBackend())
    m = sched.run_cycle()
    assert m.bound == 20
    assert sched.metrics.snapshot()["scheduler_backend_fallbacks_total"] == 1


def test_sample_policy_reference_semantics():
    # Plentiful cluster: random sampling binds everything, like the reference
    # would given feasible candidates.
    api = make_cluster_api(10, 30, selector_fraction=0.0)
    sched = Scheduler(api, NativeBackend(), policy="sample", rng=random.Random(0))
    m = sched.run_cycle()
    assert m.bound == 30
    assert m.backend == "sample×5"


def test_sample_policy_ledger_prevents_oversubscription():
    # One node with 4 cores, ten 1-core pods: without the assumed-resources
    # ledger all ten would "fit" (the reference's TOCTOU race); with it,
    # exactly 4 bind.
    api = FakeApiServer()
    api.create_node(make_node("n", cpu="4", memory="64Gi"))
    for i in range(10):
        api.create_pod(make_pod(f"p{i}", cpu="1", memory="1Gi"))
    sched = Scheduler(api, NativeBackend(), policy="sample", rng=random.Random(1))
    m = sched.run_cycle()
    assert m.bound == 4
    assert m.unschedulable == 6


def test_bound_pods_skipped():
    # A pod that is Pending but already has nodeName set is skipped
    # (reference main.rs:74-76).
    api = FakeApiServer()
    api.create_node(make_node("n", cpu="8", memory="32Gi"))
    api.create_pod(make_pod("already", node_name="n", phase="Pending"))
    sched = Scheduler(api, NativeBackend())
    m = sched.run_cycle()
    assert m.pending == 0 and m.bound == 0


def test_run_until_settled():
    api = make_cluster_api(10, 50)
    sched = Scheduler(api, NativeBackend())
    metrics = sched.run(until_settled=True)
    assert sum(m.bound for m in metrics) == 50
    assert metrics[-1].bound == 0  # settled


def test_cli_end_to_end_native():
    out = subprocess.run(
        [sys.executable, "-m", "tpu_scheduler.cli", "--backend=native", "--nodes", "10", "--pods", "50", "--seed", "3"],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    summary = lines[-1]
    assert summary["summary"] is True
    assert summary["bound_total"] == 50
    assert summary["backend"] == "native"


def test_cli_rejects_bad_backend():
    out = subprocess.run(
        [sys.executable, "-m", "tpu_scheduler.cli", "--backend=cuda"],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 2
    assert "invalid choice" in out.stderr

def test_cli_max_rounds_flag():
    """--max-rounds reaches the profile the scheduler runs, and a capped TPU
    backend (forced-CPU jax) still binds all 50 pods."""
    from tpu_scheduler import cli

    flags = ["--backend=tpu", "--nodes", "10", "--pods", "50", "--seed", "3", "--max-rounds", "2"]
    _api, sched = cli.build_scheduler(cli.build_parser().parse_args(flags))
    try:
        assert sched.profile.max_rounds == 2
    finally:
        sched.close()
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-m", "tpu_scheduler.cli", *flags], capture_output=True, text=True, cwd=cwd, env=env
    )
    assert out.returncode == 0, out.stderr
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["bound_total"] == 50


def test_backend_fallback_annotates_cycle_record():
    api = make_cluster_api(6, 20)
    sched = Scheduler(api, ExplodingBackend(), fallback_backend=NativeBackend())
    sched.run_cycle()
    rec = sched.recorder.cycles(1)[0]
    assert any("backend-fallback" in note for note in rec.get("notes", []))


def test_gang_refusal_recorded_on_timelines():
    api = FakeApiServer()
    api.load(
        nodes=[make_node("n1", cpu=2, memory="4Gi")],
        pods=[make_pod(f"w{i}", cpu="1", memory="1Gi", gang="job-1") for i in range(4)],
    )
    sched = Scheduler(api, NativeBackend())
    m = sched.run_cycle()
    assert m.bound == 0  # capacity for 2 of 4: all-or-nothing refuses whole
    tl = sched.recorder.timeline("default/w0")
    assert "gang-refused" in [e["kind"] for e in tl]
    assert sched.metrics.snapshot()["scheduler_gang_rejections_total"] == 1


def test_requeue_reason_classification():
    assert Scheduler._requeue_reason_class("api-error: 503 boom") == "api-error"
    assert Scheduler._requeue_reason_class("network-error: BrokenPipeError: x") == "network-error"
    assert Scheduler._requeue_reason_class("async-bind-failed: ApiError: x") == "binding-failed"
    assert Scheduler._requeue_reason_class("create-binding-failed: node gone") == "binding-failed"
    assert Scheduler._requeue_reason_class("gang split across scheduling scopes; retry as a unit") == "gang"
    from tpu_scheduler.errors import CreateBindingFailed, NoNodeFound

    assert Scheduler._requeue_reason_class(NoNodeFound("none")) == "no-node"
    assert Scheduler._requeue_reason_class(CreateBindingFailed("x")) == "binding-failed"
    assert Scheduler._requeue_reason_class("something else") == "other"
