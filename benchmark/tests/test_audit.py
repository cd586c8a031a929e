"""The reference judges every pod left pending, and the time to bind counts
each one that fits until the end of the drain."""

import pytest

from benchmark import audit, cluster, harness


def _cluster():
    nodes = [cluster.make_node(f"n{i}", 4, "16Gi", labels={"zone": f"z{i % 2}"}) for i in range(4)]
    fits = [cluster.make_pod(f"fits-{i}", "100m", "128Mi") for i in range(150)]
    pinned = [cluster.make_pod(f"pinned-{i}", "100m", "128Mi", node_selector={"zone": "z9"}) for i in range(50)]
    return nodes, fits, pinned


def test_every_pending_pod_that_fits_is_found():
    nodes, fits, pinned = _cluster()
    log = [(0.0, "create", p) for p in fits + pinned]
    log.append((1.0, "checkpoint", [("default", p.metadata.name) for p in fits + pinned]))
    verdict = audit.replay(nodes, log, lambda t: 0)
    assert verdict.counts["fit_but_pending"] == len(fits)
    assert verdict.fitting == {("default", p.metadata.name) for p in fits}
    assert not verdict.correct


def test_time_to_bind_counts_each_fitting_pod_until_the_drain():
    _nodes, fits, pinned = _cluster()

    class Stub:
        log = [(2.0, "bind", (("default", "fits-0"), "n0"))]
        bind_times = harness.Run.bind_times
        window_keys = [("default", p.metadata.name) for p in fits + pinned]
        start_time = dict.fromkeys(window_keys, 1.0)
        drain_end = 11.0

    run = Stub()
    fitting = {("default", p.metadata.name) for p in fits[1:]}
    ttb = harness.time_to_bind(run, fitting)
    assert len(ttb) == len(fits)  # the pods that fit nowhere are no request the scheduler missed
    assert sorted(ttb)[0] == pytest.approx(1.0) and max(ttb) == pytest.approx(10.0)


# -- inter-pod terms with matchExpressions over any topology key: JobSet's
# exclusive placement, one job a node pool --------------------------------

POOL = "cloud.google.com/gke-nodepool"
JOB = "jobset.sigs.k8s.io/job-key"


def _req(key, op, *values):
    from tpu_scheduler.api.objects import LabelSelectorRequirement

    return LabelSelectorRequirement(key=key, operator=op, values=list(values) or None)


def _term(*exprs, key=POOL, **labels):
    from tpu_scheduler.api.objects import PodAntiAffinityTerm

    return PodAntiAffinityTerm(match_labels=labels or None, topology_key=key, match_expressions=list(exprs) or None)


def _pools(n_pools=2, per_pool=2):
    return [cluster.make_node(f"p{k}-{i}", 8, "32Gi", labels={POOL: f"pool-{k}", "rack": f"rack-{i % 2}", "name": f"p{k}-{i}"})
            for k in range(n_pools) for i in range(per_pool)]


def _member(job, i, node=None):
    """Member ``i`` of JobSet job ``job`` as exclusive placement gives it."""
    return cluster.make_pod(f"{job}-{i}", "500m", "1Gi", node_name=node, phase="Running" if node else "Pending",
                            labels={JOB: job},
                            pod_affinity=[_term(_req(JOB, "In", job))],
                            anti_affinity=[_term(_req(JOB, "Exists"), _req(JOB, "NotIn", job))])


def _replay(nodes, groups, standing=()):
    """Replay ``standing`` (bound pods), then each group of ``(pod, node)``
    binds as one cycle; the pods are created before the first cycle."""
    log = [(0.0, "create", p) for p in standing]
    log += [(0.0, "create", p) for g in groups for p, _ in g]
    log += [(g + 1.5, "bind", (("default", p.metadata.name), node)) for g, binds in enumerate(groups) for p, node in binds]
    return audit.replay(nodes, log, int)


def test_two_jobs_sharing_a_pool_break_exclusive_placement():
    nodes = _pools()
    a = [_member("job-a", i) for i in range(2)]
    b = [_member("job-b", i) for i in range(2)]
    one_cycle = _replay(nodes, [[(a[0], "p0-0"), (a[1], "p0-1"), (b[0], "p0-0"), (b[1], "p0-1")]])
    assert one_cycle.counts["inter_pod"] >= 1 and one_cycle.counts["inter_pod"] == 2
    a = [_member("job-a", i) for i in range(2)]
    b = [_member("job-b", i) for i in range(2)]
    later = _replay(nodes, [[(a[0], "p0-0"), (a[1], "p0-1")], [(b[0], "p0-0"), (b[1], "p0-1")]])
    assert later.counts["inter_pod"] == 2
    assert "anti-affinity" in later.examples["inter_pod"][0]


def test_one_job_a_pool_is_exclusive_placement():
    nodes = _pools(3)
    jobs = {j: [_member(j, i) for i in range(2)] for j in ("job-a", "job-b", "job-c")}
    groups = [[(p, f"p{k}-{i}") for i, p in enumerate(jobs[j])] for k, j in enumerate(jobs)]
    verdict = _replay(nodes, [groups[0] + groups[1], groups[2]])
    assert verdict.correct, verdict.examples
    # A job bound beside a standing one in its pool.
    standing = [_member("job-s", 0, node="p0-0")]
    late = [_member("job-d", i) for i in range(2)]
    assert _replay(nodes, [[(late[0], "p1-0"), (late[1], "p1-1")]], standing).correct
    late = [_member("job-d", i) for i in range(2)]
    # Into the standing job's pool: the member there breaks anti-affinity (and,
    # once the other places first by the bootstrap, its own job's affinity).
    assert _replay(nodes, [[(late[0], "p0-1"), (late[1], "p1-1")]], standing).counts["inter_pod"] == 1


def test_the_first_member_of_a_self_affine_job_places_by_the_bootstrap():
    nodes = _pools()
    first = _member("job-a", 0)
    assert _replay(nodes, [[(first, "p1-0")]]).correct
    # Its second member must follow it into pool-1.
    a = [_member("job-a", i) for i in range(2)]
    assert _replay(nodes, [[(a[0], "p1-0")], [(a[1], "p1-1")]]).correct
    a = [_member("job-a", i) for i in range(2)]
    assert _replay(nodes, [[(a[0], "p1-0")], [(a[1], "p0-1")]]).counts["inter_pod"] == 1
    # A pod whose affinity names another job, with none of it anywhere, has no bootstrap.
    stray = cluster.make_pod("stray", "500m", "1Gi", labels={JOB: "job-x"}, pod_affinity=[_term(_req(JOB, "In", "job-y"))])
    assert _replay(nodes, [[(stray, "p0-0")]]).counts["inter_pod"] == 1


@pytest.mark.parametrize("op, values, existing, breaks", [
    ("NotIn", ("job-a",), {JOB: "job-a"}, False),
    ("NotIn", ("job-a",), {JOB: "job-b"}, True),
    ("NotIn", ("job-a",), {}, True),  # a pod without the key is NotIn
    ("Exists", (), {JOB: "job-b"}, True),
    ("Exists", (), {"app": "web"}, False),
    ("DoesNotExist", (), {"app": "web"}, True),
    ("DoesNotExist", (), {JOB: "job-b"}, False),
    ("In", ("job-b", "job-c"), {JOB: "job-b"}, True),
    ("In", ("job-b", "job-c"), {JOB: "job-d"}, False),
])
def test_each_operator_decides_anti_affinity_both_ways(op, values, existing, breaks):
    nodes = _pools()
    placed = cluster.make_pod("placed", "500m", "1Gi", node_name="p0-0", phase="Running", labels=existing)
    incoming = cluster.make_pod("incoming", "500m", "1Gi", labels={"role": "x"}, anti_affinity=[_term(_req(JOB, op, *values))])
    assert _replay(nodes, [[(incoming, "p0-1")]], [placed]).counts["inter_pod"] == breaks
    # The same term carried by the placed pod blocks the incoming one (direction B).
    carrier = cluster.make_pod("carrier", "500m", "1Gi", node_name="p0-0", phase="Running", labels={"role": "x"},
                               anti_affinity=[_term(_req(JOB, op, *values))])
    arriving = cluster.make_pod("arriving", "500m", "1Gi", labels=existing)
    assert _replay(nodes, [[(arriving, "p0-1")]], [carrier]).counts["inter_pod"] == breaks
    # Another pool is another domain.
    incoming = cluster.make_pod("incoming", "500m", "1Gi", labels={"role": "x"}, anti_affinity=[_term(_req(JOB, op, *values))])
    assert _replay(nodes, [[(incoming, "p1-0")]], [placed]).correct


def test_match_labels_and_expressions_are_anded():
    nodes = _pools()
    placed = cluster.make_pod("placed", "500m", "1Gi", node_name="p0-0", phase="Running", labels={"app": "web", JOB: "job-b"})
    term = _term(_req(JOB, "NotIn", "job-b"), app="web")  # app=web AND job-key not job-b: no match
    incoming = cluster.make_pod("incoming", "500m", "1Gi", anti_affinity=[term])
    assert _replay(nodes, [[(incoming, "p0-1")]], [placed]).correct
    term = _term(_req(JOB, "NotIn", "job-c"), app="web")
    incoming = cluster.make_pod("incoming", "500m", "1Gi", anti_affinity=[term])
    assert _replay(nodes, [[(incoming, "p0-1")]], [placed]).counts["inter_pod"] == 1


def test_a_term_over_any_node_label_counts_the_pods_bound_before_it():
    """The rack key is indexed when the first term names it; the plain pods
    bound before then count in its domains."""
    from tpu_scheduler.api.objects import TopologySpreadConstraint

    nodes = _pools(2, 4)  # racks 0 and 1 in both pools
    standing = [cluster.make_pod(f"web-{i}", "100m", "128Mi", node_name="p0-0", phase="Running", labels={"app": "web"}) for i in range(2)]
    near = cluster.make_pod("near", "100m", "128Mi", anti_affinity=[_term(key="rack", app="web")])
    assert _replay(nodes, [[(near, "p1-2")]], standing).counts["inter_pod"] == 1  # p1-2 is in rack-0
    near = cluster.make_pod("near", "100m", "128Mi", anti_affinity=[_term(key="rack", app="web")])
    assert _replay(nodes, [[(near, "p1-1")]], standing).correct  # rack-1
    spread = [TopologySpreadConstraint(topology_key="rack", max_skew=1, match_labels={"app": "web"})]
    pods = [cluster.make_pod(f"s-{i}", "100m", "128Mi", labels={"app": "web"}, topology_spread=spread) for i in range(2)]
    assert _replay(nodes, [[(pods[0], "p0-1"), (pods[1], "p1-1")]], standing).correct  # rack-1 evens the two up
    pods = [cluster.make_pod(f"s-{i}", "100m", "128Mi", labels={"app": "web"}, topology_spread=spread) for i in range(2)]
    assert _replay(nodes, [[(pods[0], "p0-2"), (pods[1], "p1-1")]], standing).counts["inter_pod"] == 1  # rack-0 now 3


def test_an_empty_selector_matches_nothing():
    nodes = _pools()
    placed = cluster.make_pod("placed", "500m", "1Gi", node_name="p0-0", phase="Running", labels={JOB: "job-b"})
    incoming = cluster.make_pod("incoming", "500m", "1Gi", anti_affinity=[_term()])
    assert _replay(nodes, [[(incoming, "p0-0")]], [placed]).correct


def test_standing_pods_with_terms_switch_the_inter_pod_replay_on():
    nodes = _pools()
    carrier = cluster.make_pod("carrier", "500m", "1Gi", node_name="p0-0", phase="Running", labels={"app": "db"},
                               anti_affinity=[_term(app="web")])
    plain = cluster.make_pod("plain", "500m", "1Gi", labels={"app": "web"})
    verdict = _replay(nodes, [[(plain, "p0-1")]], [carrier])
    assert verdict.inter_pod and verdict.counts["inter_pod"] == 1
    plain = cluster.make_pod("plain", "500m", "1Gi", labels={"app": "web"})
    assert not _replay(nodes, [[(plain, "p0-1")]]).inter_pod


def test_inter_pod_terms_are_judged_within_one_namespace():
    nodes = _pools()
    pod = cluster.make_pod("a", "500m", "1Gi", anti_affinity=[_term(app="web")])
    other = cluster.make_pod("b", "500m", "1Gi", labels={"app": "web"})
    other.metadata.namespace = "team-b"
    with pytest.raises(ValueError, match="namespace"):
        audit.replay(nodes, [(0.0, "create", pod), (0.0, "create", other)], int)


# -- where the configuration guarantees it, the checkpoint judges pending
# gangs whose room only shrank ---------------------------------------------


def _job(job, n, cpu="500m"):
    """A pending JobSet job of ``n`` pods, a gang, with exclusive placement."""
    return [cluster.make_pod(f"{job}-{i}", cpu, "1Gi", labels={JOB: job}, gang=job,
                             pod_affinity=[_term(_req(JOB, "In", job))],
                             anti_affinity=[_term(_req(JOB, "Exists"), _req(JOB, "NotIn", job))]) for i in range(n)]


def _checkpoint(nodes, pending, standing=(), judge_gangs=True):
    log = [(0.0, "create", p) for p in list(standing) + list(pending)]
    log.append((1.0, "checkpoint", [("default", p.metadata.name) for p in pending]))
    return audit.replay(nodes, log, int, judge_gangs)


def test_a_pending_gang_that_fits_a_free_pool_is_found_once():
    nodes = _pools(3)
    standing = [_member("job-s", 0, node="p0-0")]
    a, b = _job("job-a", 3), _job("job-b", 2)
    assert _checkpoint(nodes, a + b, standing, judge_gangs=False).correct  # a guarantee the configuration does not state
    verdict = _checkpoint(nodes, a + b, standing)
    # Each gang is judged alone against the state now: both fit pool-1.
    assert verdict.counts["fit_but_pending"] == 2
    assert verdict.fitting == {("default", p.metadata.name) for p in a + b}
    assert verdict.examples["fit_but_pending"][0] == "gang job-a (3 pods) fits p1-0"
    taken = standing + [_member("job-t", 0, node="p1-1"), _member("job-u", 0, node="p2-0")]
    assert _checkpoint(nodes, _job("job-a", 3), taken).correct  # every pool holds another job


def test_each_member_fits_in_the_room_the_ones_before_it_leave():
    """Pool p1 has two nodes with room for one member each, pool p0 one.  (The
    first member takes the first node that fits it, and no other: where that
    is in a pool too small for the rest, the gang reads as fitting nowhere,
    a fit missed, never one made up.)"""
    nodes = [cluster.make_node(name, 1, "32Gi", labels={POOL: pool, "name": name}) for name, pool in (("b", "p1"), ("c", "p1"), ("a", "p0"))]
    assert _checkpoint(nodes[2:], _job("job-a", 2, cpu="600m")).correct
    verdict = _checkpoint(nodes, _job("job-a", 2, cpu="600m"))
    assert verdict.counts["fit_but_pending"] == 1 and verdict.examples["fit_but_pending"] == ["gang job-a (2 pods) fits b"]


def test_a_partly_bound_gang_is_partial_and_not_judged_to_fit():
    nodes = _pools()
    a = _job("job-a", 2)
    log = [(0.0, "create", p) for p in a] + [(1.0, "bind", (("default", "job-a-0"), "p0-0"))]
    log.append((2.0, "checkpoint", [("default", p.metadata.name) for p in a]))
    verdict = audit.replay(nodes, log, int)
    assert verdict.counts["gangs_partial"] == 1 and verdict.counts["fit_but_pending"] == 0


def test_gangs_whose_room_may_have_grown_and_single_pods_with_terms_are_not_judged():
    """Pod affinity that a placed pod matches, and a hard spread, can gain
    room as a wave is placed: such a gang may have fitted nowhere when the
    scheduler passed it over.  Anti-affinity only takes room away.  A pending
    pod of no gang is judged only without inter-pod terms."""
    from tpu_scheduler.api.objects import TopologySpreadConstraint

    def gang(name, **terms):
        return [cluster.make_pod(f"{name}-{i}", "500m", "1Gi", labels={"app": name}, gang=name, **terms) for i in range(2)]

    nodes = _pools()
    web = cluster.make_pod("web", "500m", "1Gi", node_name="p0-0", phase="Running", labels={"app": "web"})
    assert _checkpoint(nodes, gang("follows", pod_affinity=[_term(app="web")]), [web]).correct
    spread = [TopologySpreadConstraint(topology_key=POOL, max_skew=1, match_labels={"app": "web"})]
    assert _checkpoint(nodes, gang("spreads", topology_spread=spread), [web]).correct
    verdict = _checkpoint(nodes, gang("avoids", anti_affinity=[_term(app="web")]), [web])
    assert verdict.counts["fit_but_pending"] == 1 and verdict.examples["fit_but_pending"] == ["gang avoids (2 pods) fits p1-0"]
    # With nothing placed that it matches, pod affinity places by the bootstrap.
    assert _checkpoint(nodes, gang("db", pod_affinity=[_term(app="db")]), [web]).counts["fit_but_pending"] == 1
    single = cluster.make_pod("single", "500m", "1Gi", anti_affinity=[_term(app="web")])
    assert _checkpoint(nodes, [single], [web]).correct
