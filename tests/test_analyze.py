"""Tests for the scripts/analyze static-analysis suite.

Every rule gets at least one true-positive fixture and one
false-positive-guard fixture; the repo-invariant passes (THRD/JAXP/DTRM)
additionally prove they catch seeded violations the OLD monolithic lint.py
(whose rule set survives as the hygiene/exports/catalogues passes) sailed
past.  The baseline contract is pinned both in-unit and against the real
tree: baseline.json entries must match current findings exactly — no new
findings, no stale pins.
"""

from __future__ import annotations

import ast
import json
import pathlib
import subprocess
import sys
import threading

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from scripts.analyze import catalogues, determinism, excp, exports, hygiene, jitpure, locks, shapes  # noqa: E402
from scripts.analyze.baseline import compare, load_baseline  # noqa: E402
from scripts.analyze.core import DEFAULT_PATHS, Context, SourceFile, load_files  # noqa: E402
from scripts.analyze.driver import PASSES, all_codes, changed_paths, file_scoped_codes, run_passes  # noqa: E402

LEGACY_PASSES = (hygiene, exports, catalogues)
# Exactly the monolithic lint.py's rule codes (ANLZ/THRD/JAXP/DTRM are new).
LEGACY_RULES = {"E999", "W291", "W191", "E711", "E712", "B006", "F841", "F401", "F822", "DEAD", "METR", "SIMC"}


def make_ctx(*files: tuple[str, str], readme: str = "") -> Context:
    out = []
    for rel, code in files:
        try:
            tree = ast.parse(code)
        except SyntaxError:
            tree = None
        out.append(SourceFile(path=pathlib.Path(rel), rel=rel, text=code, lines=code.splitlines(), tree=tree))
    return Context(files=out, root=ROOT, readme=readme)


def rule_hits(findings, rule):
    return [f for f in findings if f.rule == rule]


def legacy_findings(ctx):
    out = []
    for p in LEGACY_PASSES:
        out.extend(f for f in p.run(ctx) if f.rule in LEGACY_RULES)
    return out


# -- hygiene rules: true positive + guard each ------------------------------


def test_e999_syntax_error_and_guard():
    ctx = make_ctx(("tpu_scheduler/bad.py", "def f(:\n"))
    assert rule_hits(run_passes(ctx), "E999")
    ctx = make_ctx(("tpu_scheduler/ok.py", "def f():\n    return 1\n"))
    assert not rule_hits(run_passes(ctx), "E999")


def test_w291_trailing_whitespace_and_guard():
    ctx = make_ctx(("m.py", "x = 1 \n"))
    assert rule_hits(hygiene.run(ctx), "W291")
    ctx = make_ctx(("m.py", "x = 1\n"))
    assert not rule_hits(hygiene.run(ctx), "W291")


def test_w191_tab_indentation_and_guard():
    ctx = make_ctx(("m.py", "if True:\n\tpass\n"))
    assert rule_hits(hygiene.run(ctx), "W191")
    ctx = make_ctx(("m.py", "if True:\n    pass\n"))
    assert not rule_hits(hygiene.run(ctx), "W191")


def test_e711_none_comparison_and_guard():
    ctx = make_ctx(("m.py", "def f(a):\n    return a == None\n"))
    assert rule_hits(hygiene.run(ctx), "E711")
    ctx = make_ctx(("m.py", "def f(a):\n    return a is None\n"))
    assert not rule_hits(hygiene.run(ctx), "E711")


def test_e712_bool_comparison_and_guard():
    ctx = make_ctx(("m.py", "def f(a):\n    return True == a\n"))  # Yoda side too
    assert rule_hits(hygiene.run(ctx), "E712")
    ctx = make_ctx(("m.py", "def f(a):\n    return bool(a)\n"))
    assert not rule_hits(hygiene.run(ctx), "E712")


def test_b006_mutable_default_and_guard():
    ctx = make_ctx(("m.py", "def f(x=[]):\n    return x\n"))
    assert rule_hits(hygiene.run(ctx), "B006")
    ctx = make_ctx(("m.py", "def f(x=()):\n    return x\n"))
    assert not rule_hits(hygiene.run(ctx), "B006")


def test_f841_unused_local_and_guard():
    ctx = make_ctx(("m.py", "def f():\n    unused = 1\n    return 2\n"))
    assert rule_hits(hygiene.run(ctx), "F841")
    # Augmented assignment is a use (ledger pattern), not a dead store.
    ctx = make_ctx(("m.py", "def f(xs):\n    total = 0\n    for x in xs:\n        total += x\n    return total\n"))
    assert not rule_hits(hygiene.run(ctx), "F841")


def test_f401_unused_import_and_guard():
    ctx = make_ctx(("m.py", "import json\nimport os\n\n\ndef f():\n    return os.getpid()\n"))
    hits = rule_hits(hygiene.run(ctx), "F401")
    assert len(hits) == 1 and "'json'" in hits[0].message
    # __init__.py re-exports are exempt.
    ctx = make_ctx(("tpu_scheduler/x/__init__.py", "import json\n"))
    assert not rule_hits(hygiene.run(ctx), "F401")


def test_f822_phantom_export_and_guard():
    ctx = make_ctx(("m.py", '__all__ = ["ghost"]\n'))
    assert rule_hits(hygiene.run(ctx), "F822")
    ctx = make_ctx(("m.py", '__all__ = ["real"]\n\n\ndef real():\n    return 1\n'))
    assert not rule_hits(hygiene.run(ctx), "F822")


def test_e722_bare_except_and_guard():
    ctx = make_ctx(("m.py", "try:\n    x = 1\nexcept:\n    pass\n"))
    assert rule_hits(hygiene.run(ctx), "E722")
    ctx = make_ctx(("m.py", "try:\n    x = 1\nexcept ValueError:\n    pass\n"))
    assert not rule_hits(hygiene.run(ctx), "E722")


def test_e741_ambiguous_name_and_guard():
    ctx = make_ctx(("m.py", "def f(items):\n    l = len(items)\n    return l\n"))
    hits = rule_hits(hygiene.run(ctx), "E741")
    assert len(hits) == 1 and "'l'" in hits[0].message
    # argument form too
    ctx = make_ctx(("m.py", "def f(I):\n    return I\n"))
    assert rule_hits(hygiene.run(ctx), "E741")
    ctx = make_ctx(("m.py", "def f(items):\n    line = len(items)\n    return line\n"))
    assert not rule_hits(hygiene.run(ctx), "E741")


def test_hygiene_covers_tests_and_scripts_trees():
    """The E-/W-/F-series run over the WHOLE analyzed tree — a violation
    seeded under tests/ or scripts/ must be flagged exactly like one in the
    package (this is the coverage guarantee the hygiene docstring pins)."""
    for rel in ("tests/test_seeded.py", "scripts/seeded.py", "tpu_scheduler/seeded.py"):
        ctx = make_ctx((rel, "import json\n\n\ndef f(x=[]):\n    unused = x == None\n    return x \n"))
        found = {f.rule for f in hygiene.run(ctx)}
        assert {"F401", "B006", "E711", "W291"} <= found, (rel, found)


# -- DEAD -------------------------------------------------------------------


def test_dead_export_and_guard():
    mod = ("tpu_scheduler/widgets.py", '__all__ = ["widget"]\n\n\ndef widget():\n    return 1\n')
    ctx = make_ctx(mod)
    assert rule_hits(exports.run(ctx), "DEAD")
    ctx = make_ctx(mod, ("tests/test_widgets.py", "from tpu_scheduler.widgets import widget\n\nprint(widget())\n"))
    assert not rule_hits(exports.run(ctx), "DEAD")


# -- catalogue drift gates --------------------------------------------------


def test_metr_drift_and_guard():
    mod = ("tpu_scheduler/m.py", 'NAME = "scheduler_phantom_total"\n')
    assert rule_hits(catalogues.run(make_ctx(mod, readme="")), "METR")
    assert not rule_hits(catalogues.run(make_ctx(mod, readme="... scheduler_phantom_total ...")), "METR")


def test_simc_drift_and_guard():
    mod = (
        "tpu_scheduler/sim/scenarios.py",
        'def _register(s):\n    return s\n\n\n_register(Scenario(name="ghost-scenario"))\n',
    )
    assert rule_hits(catalogues.run(make_ctx(mod, readme="")), "SIMC")
    assert not rule_hits(catalogues.run(make_ctx(mod, readme="| ghost-scenario |")), "SIMC")


def test_resc_drift_and_guard():
    mod = (
        "tpu_scheduler/runtime/resilience.py",
        "DEFAULT_POLICIES = {\"ghost-class\": None}\n"
        "STATES = (\"closed\", \"ghost-state\")\n"
        "class BreakerConfig:\n    ghost_knob: int = 1\n",
    )
    hits = rule_hits(catalogues.run(make_ctx(mod, readme="closed")), "RESC")
    assert {h.message.split("'")[1] for h in hits} == {"ghost-class", "ghost-state", "ghost_knob"}
    ok_readme = "closed ghost-class ghost-state ghost_knob"
    assert not rule_hits(catalogues.run(make_ctx(mod, readme=ok_readme)), "RESC")


def test_topo_drift_and_guard():
    model_mod = (
        "tpu_scheduler/topology/model.py",
        'DEFAULT_LEVEL_KEYS = (("ghost-level", "topology.x/ghost-key"),)\n',
    )
    knob_mod = ("tpu_scheduler/topology/locality.py", 'SCORING_KNOBS = ("ghost_knob",)\n')
    sc_mod = (
        "tpu_scheduler/sim/scenarios.py",
        '_register(Scenario(name="ghost-topo-scenario", workload=WorkloadSpec(rack_size=4)))\n'
        '_register(Scenario(name="plain-scenario", workload=WorkloadSpec(arrival_rate=1.0)))\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(model_mod, knob_mod, sc_mod, readme="")), "TOPO")
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-level",
        "topology.x/ghost-key",
        "ghost_knob",
        "ghost-topo-scenario",  # plain-scenario is SIMC's business, not TOPO's
    }
    ok = "ghost-level topology.x/ghost-key ghost_knob ghost-topo-scenario"
    assert not rule_hits(catalogues.run(make_ctx(model_mod, knob_mod, sc_mod, readme=ok)), "TOPO")


def test_topo_real_tree_is_catalogued():
    files = load_files(["tpu_scheduler/topology", "tpu_scheduler/sim/scenarios.py"])
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "TOPO")
    assert not hits, "; ".join(h.render() for h in hits)


def test_repl_drift_and_guard():
    shards_mod = (
        "tpu_scheduler/runtime/shards.py",
        'SHARD_LEASE_PREFIX = "ghost-shard-"\nREPLICA_LEASE_PREFIX = "ghost-presence-"\nOTHER = "not-a-prefix"\n',
    )
    multi_mod = ("tpu_scheduler/sim/multi.py", 'AVAILABILITY_FIELDS = ("ghost_takeover_field",)\n')
    sc_mod = (
        "tpu_scheduler/sim/scenarios.py",
        '_register(Scenario(name="ghost-replica-scenario", replicas=2))\n'
        '_register(Scenario(name="plain-scenario", workload=WorkloadSpec(arrival_rate=1.0)))\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(shards_mod, multi_mod, sc_mod, readme="")), "REPL")
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-shard-",
        "ghost-presence-",
        "ghost_takeover_field",
        "ghost-replica-scenario",  # plain-scenario is SIMC's business, not REPL's
    }
    ok = "ghost-shard- ghost-presence- ghost_takeover_field ghost-replica-scenario"
    assert not rule_hits(catalogues.run(make_ctx(shards_mod, multi_mod, sc_mod, readme=ok)), "REPL")


def test_repl_real_tree_is_catalogued():
    files = load_files(
        ["tpu_scheduler/runtime/shards.py", "tpu_scheduler/sim/multi.py", "tpu_scheduler/sim/scenarios.py"]
    )
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "REPL")
    assert not hits, "; ".join(h.render() for h in hits)


def test_prof_drift_and_guard():
    mod = (
        "tpu_scheduler/utils/profiler.py",
        'SPAN_CATALOGUE = ("ghost-span",)\n'
        'SLO_TIERS = (("ghost-tier", 100, 60.0),)\n'
        'OTHER = ("not-a-span",)\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(mod, readme="")), "PROF")
    assert {h.message.split("'")[1] for h in hits} == {"ghost-span", "ghost-tier"}
    ok = "ghost-span ghost-tier"
    assert not rule_hits(catalogues.run(make_ctx(mod, readme=ok)), "PROF")


def test_prof_real_tree_is_catalogued():
    files = load_files(["tpu_scheduler/utils/profiler.py"])
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "PROF")
    assert not hits, "; ".join(h.render() for h in hits)


def test_dlta_drift_and_guard():
    eng_mod = (
        "tpu_scheduler/delta/engine.py",
        'ESCALATION_REASONS = ("ghost-trigger",)\nOTHER = ("not-a-trigger",)\n',
    )
    sc_mod = (
        "tpu_scheduler/sim/scorecard.py",
        'INCREMENTAL_FIELDS = ("ghost_incremental_field",)\nSCORECARD_FIELDS = ("simc_business",)\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(eng_mod, sc_mod, readme="")), "DLTA")
    # simc_business is SIMC's token, not DLTA's; OTHER is not a catalogue tuple.
    assert {h.message.split("'")[1] for h in hits} == {"ghost-trigger", "ghost_incremental_field"}
    ok = "ghost-trigger ghost_incremental_field"
    assert not rule_hits(catalogues.run(make_ctx(eng_mod, sc_mod, readme=ok)), "DLTA")


def test_dlta_real_tree_is_catalogued():
    files = load_files(["tpu_scheduler/delta/engine.py", "tpu_scheduler/sim/scorecard.py"])
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "DLTA")
    assert not hits, "; ".join(h.render() for h in hits)


def test_rebl_drift_and_guard():
    planner_mod = (
        "tpu_scheduler/rebalance/planner.py",
        'MIGRATION_REASONS = ("ghost-migration-reason",)\n'
        'SKIP_REASONS = ("ghost-skip-reason",)\n'
        "class RebalanceConfig:\n    ghost_knob: int = 1\n"
        'OTHER = ("not-a-reason",)\n',
    )
    sc_mod = (
        "tpu_scheduler/sim/scorecard.py",
        'REBALANCE_FIELDS = ("ghost_rebalance_field",)\nSCORECARD_FIELDS = ("simc_business",)\n',
    )
    scen_mod = (
        "tpu_scheduler/sim/scenarios.py",
        '_register(Scenario(name="ghost-defrag-scenario", rebalance=True))\n'
        '_register(Scenario(name="plain-scenario", workload=WorkloadSpec(arrival_rate=1.0)))\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(planner_mod, sc_mod, scen_mod, readme="")), "REBL")
    # simc_business is SIMC's token and plain-scenario SIMC's scenario;
    # OTHER is not a taxonomy tuple — none of them are REBL's business.
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-migration-reason",
        "ghost-skip-reason",
        "ghost_knob",
        "ghost_rebalance_field",
        "ghost-defrag-scenario",
    }
    ok = "ghost-migration-reason ghost-skip-reason ghost_knob ghost_rebalance_field ghost-defrag-scenario"
    assert not rule_hits(catalogues.run(make_ctx(planner_mod, sc_mod, scen_mod, readme=ok)), "REBL")


def test_rebl_real_tree_is_catalogued():
    files = load_files(
        ["tpu_scheduler/rebalance/planner.py", "tpu_scheduler/sim/scorecard.py", "tpu_scheduler/sim/scenarios.py"]
    )
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "REBL")
    assert not hits, "; ".join(h.render() for h in hits)


def test_flet_drift_and_guard():
    keyer_mod = (
        "tpu_scheduler/fleet/keyer.py",
        'KEYER_MODES = ("ghost-keyer-mode",)\nOTHER = ("not-a-mode",)\n',
    )
    res_mod = (
        "tpu_scheduler/fleet/reservation.py",
        'RESERVATION_STATES = ("ghost-reservation-state",)\n'
        'GANG_RESERVATION_PREFIX = "ghost-gang-"\n'
        'NOT_A_LEASE = "plain-string"\n',
    )
    resize_mod = ("tpu_scheduler/fleet/resize.py", 'SHARD_MAP_LEASE = "ghost-shard-map"\n')
    hits = rule_hits(catalogues.run(make_ctx(keyer_mod, res_mod, resize_mod, readme="")), "FLET")
    # OTHER / NOT_A_LEASE are not catalogue constants — not FLET's business.
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-keyer-mode",
        "ghost-reservation-state",
        "ghost-gang-",
        "ghost-shard-map",
    }
    ok = "ghost-keyer-mode ghost-reservation-state ghost-gang- ghost-shard-map"
    assert not rule_hits(catalogues.run(make_ctx(keyer_mod, res_mod, resize_mod, readme=ok)), "FLET")


def test_flet_real_tree_is_catalogued():
    files = load_files(
        ["tpu_scheduler/fleet/keyer.py", "tpu_scheduler/fleet/reservation.py", "tpu_scheduler/fleet/resize.py"]
    )
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "FLET")
    assert not hits, "; ".join(h.render() for h in hits)


def test_lern_drift_and_guard():
    obj_mod = (
        "tpu_scheduler/learn/objective.py",
        'OBJECTIVE_COMPONENTS = (("ghost-objective-component", 1.0),)\n'
        'POLICY_FIELDS = ("ghost_policy_field",)\n'
        'OTHER = ("not-a-component",)\n',
    )
    env_mod = (
        "tpu_scheduler/learn/env.py",
        'OBSERVATION_FIELDS = ("ghost_observation_field",)\n'
        'ACTION_KNOBS = (("ghost_action_knob", 0.0, 1.0),)\n',
    )
    search_mod = (
        "tpu_scheduler/learn/search.py",
        "class SearchConfig:\n    ghost_search_knob: int = 3\n\n\nclass Other:\n    not_a_knob: int = 1\n",
    )
    prof_mod = (
        "tpu_scheduler/models/profiles.py",
        'ARTIFACT_FIELDS = ("ghost_artifact_field",)\nNOT_AN_ENVELOPE = ("plain",)\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(obj_mod, env_mod, search_mod, prof_mod, readme="")), "LERN")
    # OTHER / Other.not_a_knob / NOT_AN_ENVELOPE are not catalogue surface.
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-objective-component",
        "ghost_policy_field",
        "ghost_observation_field",
        "ghost_action_knob",
        "ghost_search_knob",
        "ghost_artifact_field",
    }
    ok = (
        "ghost-objective-component ghost_policy_field ghost_observation_field "
        "ghost_action_knob ghost_search_knob ghost_artifact_field"
    )
    assert not rule_hits(catalogues.run(make_ctx(obj_mod, env_mod, search_mod, prof_mod, readme=ok)), "LERN")


def test_lern_real_tree_is_catalogued():
    files = load_files(
        [
            "tpu_scheduler/learn/objective.py",
            "tpu_scheduler/learn/env.py",
            "tpu_scheduler/learn/search.py",
            "tpu_scheduler/models/profiles.py",
        ]
    )
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "LERN")
    assert not hits, "; ".join(h.render() for h in hits)


def test_latn_drift_and_guard():
    events_mod = (
        "tpu_scheduler/utils/events.py",
        'SEGMENTS = ("ghost-segment",)\nEVENT_KINDS = ("not-a-segment",)\n',
    )
    sc_mod = (
        "tpu_scheduler/sim/scorecard.py",
        'LATENCY_FIELDS = ("ghost_latency_field",)\nOTHER_FIELDS = ("plain",)\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(events_mod, sc_mod, readme="")), "LATN")
    # EVENT_KINDS / OTHER_FIELDS are not LATN catalogue surface.
    assert {h.message.split("'")[1] for h in hits} == {"ghost-segment", "ghost_latency_field"}
    ok = "ghost-segment ghost_latency_field"
    assert not rule_hits(catalogues.run(make_ctx(events_mod, sc_mod, readme=ok)), "LATN")


def test_latn_real_tree_is_catalogued():
    files = load_files(["tpu_scheduler/utils/events.py", "tpu_scheduler/sim/scorecard.py"])
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "LATN")
    assert not hits, "; ".join(h.render() for h in hits)


def test_elas_drift_and_guard():
    policy_mod = (
        "tpu_scheduler/autoscale/policy.py",
        'SKIP_REASONS = ("ghost-elas-skip",)\n'
        "class AutoscaleConfig:\n    ghost_elas_knob: int = 1\n"
        'OTHER = ("not-a-reason",)\n',
    )
    provider_mod = (
        "tpu_scheduler/autoscale/provider.py",
        'DEFAULT_CATALOG = (InstanceSKU(name="ghost-sku", cpu=8),)\n'
        'OTHER = InstanceSKU(cpu=8)\n',
    )
    sc_mod = (
        "tpu_scheduler/sim/scorecard.py",
        'ELASTICITY_FIELDS = ("ghost_elasticity_field",)\nSCORECARD_FIELDS = ("simc_business",)\n',
    )
    scen_mod = (
        "tpu_scheduler/sim/scenarios.py",
        '_register(Scenario(name="ghost-elastic-scenario", autoscale=True))\n'
        '_register(Scenario(name="plain-scenario", workload=WorkloadSpec(arrival_rate=1.0)))\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(policy_mod, provider_mod, sc_mod, scen_mod, readme="")), "ELAS")
    # simc_business is SIMC's token and plain-scenario SIMC's scenario;
    # OTHER and the name-less InstanceSKU are not ELAS catalogue surface.
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-elas-skip",
        "ghost_elas_knob",
        "ghost-sku",
        "ghost_elasticity_field",
        "ghost-elastic-scenario",
    }
    ok = "ghost-elas-skip ghost_elas_knob ghost-sku ghost_elasticity_field ghost-elastic-scenario"
    assert not rule_hits(catalogues.run(make_ctx(policy_mod, provider_mod, sc_mod, scen_mod, readme=ok)), "ELAS")


def test_elas_real_tree_is_catalogued():
    files = load_files(
        [
            "tpu_scheduler/autoscale/policy.py",
            "tpu_scheduler/autoscale/provider.py",
            "tpu_scheduler/sim/scorecard.py",
            "tpu_scheduler/sim/scenarios.py",
        ]
    )
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "ELAS")
    assert not hits, "; ".join(h.render() for h in hits)


def test_fuzz_drift_and_guard():
    plan_mod = (
        "tpu_scheduler/sim/fuzz/plan.py",
        'FAULT_OPS = ("ghost-meteor",)\n'
        'PLAN_FIELDS = ("ghost_plan_field",)\n'
        'OP_FIELDS = ("ghost_op_field",)\n'
        'BASE_WORKLOADS = {"ghost-base": None}\n'
        'OTHER = ("not-a-fault",)\n',
    )
    cov_mod = (
        "tpu_scheduler/sim/fuzz/coverage.py",
        'STATE_FACETS = ("ghost-facet",)\n',
    )
    corpus_mod = (
        "tpu_scheduler/sim/fuzz/corpus.py",
        'ENTRY_FIELDS = ("ghost_entry_field",)\n',
    )
    sc_mod = (
        "tpu_scheduler/sim/scorecard.py",
        'CONVERGENCE_FIELDS = ("ghost_convergence_field",)\nSCORECARD_FIELDS = ("simc_business",)\n',
    )
    hits = rule_hits(catalogues.run(make_ctx(plan_mod, cov_mod, corpus_mod, sc_mod, readme="")), "FUZZ")
    # simc_business belongs to SIMC; OTHER is not fuzz catalogue surface.
    assert {h.message.split("'")[1] for h in hits} == {
        "ghost-meteor",
        "ghost_plan_field",
        "ghost_op_field",
        "ghost-base",
        "ghost-facet",
        "ghost_entry_field",
        "ghost_convergence_field",
    }
    ok = (
        "ghost-meteor ghost_plan_field ghost_op_field ghost-base "
        "ghost-facet ghost_entry_field ghost_convergence_field"
    )
    assert not rule_hits(catalogues.run(make_ctx(plan_mod, cov_mod, corpus_mod, sc_mod, readme=ok)), "FUZZ")


def test_fuzz_real_tree_is_catalogued():
    files = load_files(
        [
            "tpu_scheduler/sim/fuzz/plan.py",
            "tpu_scheduler/sim/fuzz/coverage.py",
            "tpu_scheduler/sim/fuzz/corpus.py",
            "tpu_scheduler/sim/scorecard.py",
        ]
    )
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    hits = rule_hits(catalogues.run(ctx), "FUZZ")
    assert not hits, "; ".join(h.render() for h in hits)


def test_anlz_drift_and_guard():
    codes = sorted(all_codes())
    partial_readme = " ".join(c for c in codes if c != "DTRM")
    hits = rule_hits(catalogues.run(make_ctx(readme=partial_readme)), "ANLZ")
    assert len(hits) == 1 and "'DTRM'" in hits[0].message
    assert not rule_hits(catalogues.run(make_ctx(readme=" ".join(codes))), "ANLZ")


# -- THRD lock discipline ---------------------------------------------------

THRD_BAD = """import threading


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []  # guarded-by: _lock

    def bad(self):
        self.items.append(2)
"""

THRD_GOOD = """import threading


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self.items = []  # guarded-by: _lock

    def good(self):
        with self._lock:
            self.items.append(1)

    def good_via_condition(self):
        with self._cv:
            self.items.append(2)

    def helper(self):  # holds-lock: _lock
        self.items.clear()

    def good_call(self):
        with self._lock:
            self.helper()
"""


def test_thrd_unguarded_access_caught_but_old_lint_passed():
    ctx = make_ctx(("tpu_scheduler/runtime/c.py", THRD_BAD))
    assert not legacy_findings(ctx), "the old lint.py rule set must pass this snippet"
    hits = rule_hits(locks.run(ctx), "THRD")
    assert len(hits) == 1 and "'items'" in hits[0].message and "outside" in hits[0].message


def test_thrd_guards_with_block_condition_alias_and_holds_lock():
    ctx = make_ctx(("tpu_scheduler/runtime/c.py", THRD_GOOD))
    assert not rule_hits(locks.run(ctx), "THRD")


def test_thrd_holds_lock_call_site_check():
    code = THRD_GOOD + "\n    def bad_call(self):\n        self.helper()\n"
    hits = rule_hits(locks.run(make_ctx(("tpu_scheduler/runtime/c.py", code))), "THRD")
    assert len(hits) == 1 and "helper()" in hits[0].message


def test_thrd_plain_lock_reentry_is_deadlock():
    code = (
        "import threading\n\n\nclass C:\n"
        "    def __init__(self):\n        self._lock = threading.Lock()\n"
        "    def boom(self):\n        with self._lock:\n            with self._lock:\n                pass\n"
    )
    hits = rule_hits(locks.run(make_ctx(("m.py", code))), "THRD")
    assert len(hits) == 1 and "deadlock" in hits[0].message
    # RLock re-entry is legal — the guard case.
    hits = rule_hits(locks.run(make_ctx(("m.py", code.replace("Lock()", "RLock()")))), "THRD")
    assert not hits


def test_thrd_lock_order_cycle_detection_and_guard():
    cyclic = """import threading


class A:
    def __init__(self):
        self._a_lock = threading.Lock()
        self.b = None

    def one(self):
        with self._a_lock:
            self.b.two()


class B:
    def __init__(self):
        self._b_lock = threading.Lock()
        self.a = None

    def two(self):
        with self._b_lock:
            pass

    def three(self):
        with self._b_lock:
            self.a.one()
"""
    hits = rule_hits(locks.run(make_ctx(("m.py", cyclic))), "THRD")
    assert len(hits) == 1 and "cycle" in hits[0].message
    # Consistent order (B.three not taking A's lock) — no cycle.
    acyclic = cyclic.replace("    def three(self):\n        with self._b_lock:\n            self.a.one()\n", "")
    assert not rule_hits(locks.run(make_ctx(("m.py", acyclic))), "THRD")


def test_thrd_dataclass_field_annotations():
    code = """import threading
from dataclasses import dataclass, field


@dataclass
class R:
    counters: dict = field(default_factory=dict)  # guarded-by: _lock
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def bad(self):
        return len(self.counters)
"""
    hits = rule_hits(locks.run(make_ctx(("m.py", code))), "THRD")
    assert len(hits) == 1 and "'counters'" in hits[0].message


# -- JAXP jit purity --------------------------------------------------------

JAXP_BAD = """import time

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial


@partial(jax.jit, static_argnames=("n",))
def root(x, n):
    y = jnp.sum(x)
    if y > 0:
        return helper(y)
    return y


def helper(y):
    print(y)
    t = time.monotonic()
    z = np.asarray(y)
    return float(jnp.abs(y)) + y.item() + t + z
"""

JAXP_GOOD = """import jax
import jax.numpy as jnp
from functools import partial


@partial(jax.jit, static_argnames=("use_fast", "n"))
def root(x, use_fast, n):
    if use_fast:
        return jnp.sum(x[:n])
    return jnp.sum(x)


def host_driver(x):
    out = root(x, True, 4)
    return float(out) + out.item()
"""


def test_jaxp_host_syncs_caught_but_old_lint_passed():
    ctx = make_ctx(("tpu_scheduler/ops/m.py", JAXP_BAD))
    assert not legacy_findings(ctx), "the old lint.py rule set must pass this snippet"
    messages = [f.message for f in rule_hits(jitpure.run(ctx), "JAXP")]
    assert any("Python 'if' on a traced expression" in m for m in messages)
    assert any(".item() host sync" in m for m in messages)
    assert any("print() host I/O" in m for m in messages)
    assert any("time.monotonic() wall-clock" in m for m in messages)
    assert any("np.asarray() materializes a tracer" in m for m in messages)
    assert any("float() on a traced expression" in m for m in messages)


def test_jaxp_static_branches_and_host_code_not_flagged():
    ctx = make_ctx(("tpu_scheduler/ops/m.py", JAXP_GOOD))
    # Static-arg branches inside jit and syncs in UNreached host code are fine.
    assert not rule_hits(jitpure.run(ctx), "JAXP")


def test_jaxp_reaches_through_jax_jit_call_form():
    code = """import jax


def build():
    def inner(x):
        return x.item()

    return inner


fn = jax.jit(build())
"""
    hits = rule_hits(jitpure.run(make_ctx(("tpu_scheduler/ops/m.py", code))), "JAXP")
    assert len(hits) == 1 and ".item()" in hits[0].message


# -- DTRM sim determinism ---------------------------------------------------

DTRM_BAD = """import random
import time


def f(out):
    t = time.time()
    r = random.random()
    for x in {1, 2, 3}:
        out.append(x)
    return t, r
"""

DTRM_GOOD = """import random


def f(clock, rng: random.Random, out):
    t = clock()
    r = rng.random()
    seeded = random.Random(42).random()
    for x in sorted({1, 2, 3}):
        out.append(x)
    return t, r, seeded
"""


def test_dtrm_wall_clock_rng_and_set_iteration_caught_but_old_lint_passed():
    ctx = make_ctx(("tpu_scheduler/sim/mod.py", DTRM_BAD))
    assert not legacy_findings(ctx), "the old lint.py rule set must pass this snippet"
    messages = [f.message for f in rule_hits(determinism.run(ctx), "DTRM")]
    assert any("time.time()" in m for m in messages)
    assert any("random.random()" in m for m in messages)
    assert any("iteration over a set" in m for m in messages)
    assert len(messages) == 3


def test_dtrm_sanctioned_sources_not_flagged():
    assert not determinism.run(make_ctx(("tpu_scheduler/sim/mod.py", DTRM_GOOD)))


def test_dtrm_scoped_to_sim_package():
    # The same violations OUTSIDE sim/ are not DTRM's business.
    assert not determinism.run(make_ctx(("tpu_scheduler/runtime/mod.py", DTRM_BAD)))


# -- SHPE shape/dtype contracts ---------------------------------------------

SHPE_TRANSPOSED = """import jax.numpy as jnp


# shape: (mask: [P, N] bool, scores: [P, N] f32) -> [P] i64
def pick(mask, scores):
    s = jnp.where(mask, scores, -jnp.inf)
    return jnp.argmax(s, axis=1)


# shape: (mask: [P, N] bool, scores: [N, P] f32) -> [P] i64
def caller(mask, scores):
    return pick(mask, scores)
"""

SHPE_AXIS = """# shape: (scores: [P] f32) -> scalar f32
def total(scores):
    return scores.sum(axis=1)
"""

SHPE_BOOL_PROMO = """# shape: (mask: [P, N] bool, w: [P, N] f32) -> [P, N] f32
def weight(xp, mask, w):
    return mask * w
"""

SHPE_MATMUL = """# shape: (pod_sel: [P, L] f32, node_labels: [N, L] f32) -> [P, N] f32
def counts(pod_sel, node_labels):
    return pod_sel @ node_labels
"""

SHPE_CLEAN = """import jax.numpy as jnp


# shape: (mask: [P, N] bool, scores: [P, N] f32) -> [P] i64
def pick(mask, scores):
    s = jnp.where(mask, scores, -jnp.inf)
    return jnp.argmax(s, axis=1)


# shape: (mask: [P, N] bool, scores: [P, N] f32, w: [P, N] f32,
#   pod_sel: [P, L] f32, node_labels: [N, L] f32) -> [P] i64
def caller(mask, scores, w, pod_sel, node_labels):
    hits = pod_sel @ node_labels.T
    boosted = scores + w * mask.astype(jnp.float32) + hits
    return pick(mask, boosted)
"""


def shpe_hits(*files):
    return rule_hits(shapes.run(make_ctx(*files)), "SHPE")


def test_shpe_transposed_call_arg_caught_once_but_old_lint_passed():
    ctx = make_ctx(("tpu_scheduler/ops/m.py", SHPE_TRANSPOSED))
    assert not legacy_findings(ctx), "the old lint.py rule set must pass this snippet"
    hits = rule_hits(shapes.run(ctx), "SHPE")
    assert len(hits) == 1 and "transposed operand" in hits[0].message


def test_shpe_wrong_reduction_axis_caught_once():
    hits = shpe_hits(("tpu_scheduler/ops/m.py", SHPE_AXIS))
    assert len(hits) == 1 and "axis=1" in hits[0].message and "rank 1" in hits[0].message


def test_shpe_bool_mask_promotion_caught_once():
    hits = shpe_hits(("tpu_scheduler/ops/m.py", SHPE_BOOL_PROMO))
    assert len(hits) == 1 and "bool mask" in hits[0].message
    # explicit astype is the sanctioned form
    fixed = SHPE_BOOL_PROMO.replace("mask * w", "mask.astype(xp.float32) * w")
    assert not shpe_hits(("tpu_scheduler/ops/m.py", fixed))


def test_shpe_matmul_inner_mismatch_caught_once():
    hits = shpe_hits(("tpu_scheduler/ops/m.py", SHPE_MATMUL))
    assert len(hits) == 1 and "matmul inner dims differ" in hits[0].message
    fixed = SHPE_MATMUL.replace("pod_sel @ node_labels", "pod_sel @ node_labels.T")
    assert not shpe_hits(("tpu_scheduler/ops/m.py", fixed))


def test_shpe_return_drift_caught():
    code = "# shape: (x: [P, N] f32) -> [P] f32\ndef f(x):\n    return x\n"
    hits = shpe_hits(("tpu_scheduler/ops/m.py", code))
    assert len(hits) == 1 and "returns rank-2" in hits[0].message


def test_shpe_contract_rot_caught():
    code = (
        "# shape: (x: [P] floof) -> [P] f32\ndef f(x):\n    return x\n\n\n"
        "# shape: (ghost: [P] f32) -> [P] f32\ndef g(x):\n    return x\n"
    )
    msgs = [h.message for h in shpe_hits(("tpu_scheduler/ops/m.py", code))]
    assert any("malformed shape contract" in m for m in msgs)
    assert any("unknown parameter 'ghost'" in m for m in msgs)


def test_shpe_clean_pipeline_not_flagged():
    assert not shpe_hits(("tpu_scheduler/ops/m.py", SHPE_CLEAN))


def test_shpe_scalar_param_names_tie_allocation_shapes():
    code = (
        "import numpy as np\n\n\n"
        "# shape: (p_pad: int, t_pad: int) -> [p_pad, t_pad] f32\n"
        "def alloc(p_pad, t_pad):\n    return np.zeros((t_pad, p_pad), dtype=np.float32)\n"
    )
    hits = shpe_hits(("tpu_scheduler/ops/m.py", code))
    assert len(hits) == 1 and "returns [t_pad, p_pad]" in hits[0].message


def test_shpe_real_annotated_modules_are_clean():
    """FP guard over the real annotated tree: the tensor pipeline's ~75
    contracts must interpret clean (the acceptance bar for SHPE)."""
    files = load_files(
        [
            "tpu_scheduler/ops",
            "tpu_scheduler/core/predicates.py",
            "tpu_scheduler/backends",
            "tpu_scheduler/parallel/sharded.py",
            "tpu_scheduler/topology",
        ]
    )
    ctx = Context(files=files, root=ROOT, readme="")
    assert sum("# shape:" in f.text for f in files) >= 8, "annotated modules went missing"
    hits = rule_hits(shapes.run(ctx), "SHPE")
    assert not hits, "; ".join(h.render() for h in hits)


def test_shpe_topology_gather_contract_mutation_caught():
    """ISSUE 6 satellite: mutation-check a topology contract — dropping the
    per-pod gang-row gather in score_block ([G, N] broadcast straight into
    the [B, N] score) must contradict the declared `# shape:` contract."""
    path = ROOT / "tpu_scheduler" / "ops" / "score.py"
    text = path.read_text()
    ctx = make_ctx(("tpu_scheduler/ops/score.py", text))
    assert not rule_hits(shapes.run(ctx), "SHPE")
    mutated = text.replace("score + topo_gang_node[pod_gang_id]", "score + topo_gang_node")
    assert mutated != text, "the topology gather went missing from score_block"
    hits = rule_hits(shapes.run(make_ctx(("tpu_scheduler/ops/score.py", mutated))), "SHPE")
    assert len(hits) == 1 and "[G, N]" in hits[0].message and "[B, N]" in hits[0].message


# -- EXCP failure-class taxonomy closure ------------------------------------

EXCP_CONTROLLER = '''class Scheduler:
    @staticmethod
    def _requeue_reason_class(reason):
        if isinstance(reason, NoNodeFound):
            return "no-node"
        s = str(reason)
        head = s.split(":", 1)[0]
        if head in ("api-error", "network-error"):
            return head
        if "gang" in s:
            return "gang"
        return "other"
'''

EXCP_RESILIENCE = """DEFAULT_POLICIES = {
    "no-node": None,
    "api-error": None,
    "network-error": None,
    "gang": None,
    "other": None,
}
"""

EXCP_README = """| `scheduler_requeues_by_reason_total{reason=...}` | counter | `no-node`, `api-error`, `network-error`, `gang`, `other` |
| `no-node` | base | 4xbase |
| `api-error` | base/8 | 2xbase |
| `network-error` | base/8 | 2xbase |
| `gang` | base | 4xbase |
| `other` | base | 2xbase |
"""


def excp_ctx(controller=EXCP_CONTROLLER, resilience=EXCP_RESILIENCE, readme=EXCP_README):
    return make_ctx(
        ("tpu_scheduler/runtime/controller.py", controller),
        ("tpu_scheduler/runtime/resilience.py", resilience),
        readme=readme,
    )


def test_excp_closed_taxonomy_not_flagged():
    assert not rule_hits(excp.run(excp_ctx()), "EXCP")


def test_excp_missing_backoff_policy_caught_once_but_old_lint_passed():
    ctx = excp_ctx(controller=EXCP_CONTROLLER.replace('"gang"\n        return "other"', '"ghost-class"\n        return "other"'))
    assert not legacy_findings(ctx), "the old lint.py rule set must pass this snippet"
    hits = rule_hits(excp.run(ctx), "EXCP")
    policy_gaps = [h for h in hits if "has no BackoffQueue policy" in h.message]
    assert len(policy_gaps) == 1 and "'ghost-class'" in policy_gaps[0].message
    # the dropped class now also reads as a dead policy — the reverse gap
    assert any("never produced" in h.message and "'gang'" in h.message for h in hits)


def test_excp_dead_policy_caught():
    res = EXCP_RESILIENCE.replace('"other": None,', '"other": None,\n    "zombie": None,')
    hits = rule_hits(excp.run(excp_ctx(resilience=res)), "EXCP")
    assert len(hits) >= 1 and any("'zombie'" in h.message and "never produced" in h.message for h in hits)


def test_excp_readme_rows_required_both_tables():
    # strip the Resilience table row for gang: metric row keeps it
    readme = EXCP_README.replace("| `gang` | base | 4xbase |\n", "")
    hits = rule_hits(excp.run(excp_ctx(readme=readme)), "EXCP")
    assert len(hits) == 1 and "Resilience failure-class table" in hits[0].message and "'gang'" in hits[0].message
    # strip it from the metric row too
    readme2 = readme.replace("`gang`, ", "")
    hits2 = rule_hits(excp.run(excp_ctx(readme=readme2)), "EXCP")
    assert {h.message for h in hits2} > {h.message for h in hits}
    assert any("metric catalogue row" in h.message and "'gang'" in h.message for h in hits2)


def test_excp_silent_on_partial_context():
    """Without controller.py + resilience.py together the closure is
    unjudgeable — the pass must stay silent (the --changed-only contract)."""
    ctx = make_ctx(("tpu_scheduler/runtime/controller.py", EXCP_CONTROLLER), readme="")
    assert not excp.run(ctx)


def test_excp_real_tree_is_closed():
    files = load_files(["tpu_scheduler/runtime/controller.py", "tpu_scheduler/runtime/resilience.py"])
    ctx = Context(files=files, root=ROOT, readme=(ROOT / "README.md").read_text())
    hits = rule_hits(excp.run(ctx), "EXCP")
    assert not hits, "; ".join(h.render() for h in hits)


# -- baseline contract ------------------------------------------------------


def test_baseline_matches_current_findings_exactly():
    """baseline.json must pin exactly the findings the tree produces: no new
    findings, no stale entries — and zero DTRM entries in sim/ (the
    simulator is held to a clean bill, never a pinned one)."""
    files = load_files(DEFAULT_PATHS)
    readme = (ROOT / "README.md").read_text()
    ctx = Context(files=files, root=ROOT, readme=readme)
    findings = run_passes(ctx)
    entries = load_baseline()
    scope = {f.rel for f in files} | {"README.md"}
    new, stale, baselined = compare(findings, entries, paths=scope)
    assert not new, "unpinned findings: " + "; ".join(f.render() for f in new)
    assert not stale, "stale baseline entries: " + json.dumps(stale)
    assert len(baselined) == len(findings)
    assert not [
        e for e in entries if e["rule"] == "DTRM" and e["path"].startswith("tpu_scheduler/sim/")
    ], "DTRM findings in sim/ must be fixed, never baselined"
    for e in entries:
        assert len(e["reason"]) >= 20, f"baseline reasons must justify, not gesture: {e}"


def test_baseline_compare_new_and_stale_detection():
    from scripts.analyze.core import Finding

    found = [Finding("THRD", "a.py", 3, "msg-a")]
    entries = [
        {"rule": "THRD", "path": "a.py", "message": "msg-a", "reason": "pinned"},
        {"rule": "DTRM", "path": "b.py", "message": "msg-gone", "reason": "pinned"},
    ]
    new, stale, baselined = compare(found + [Finding("JAXP", "c.py", 1, "msg-new")], entries)
    assert [f.rule for f in new] == ["JAXP"]
    assert [e["rule"] for e in stale] == ["DTRM"]
    assert [f.rule for f in baselined] == ["THRD"]
    # Line numbers are not identity: a moved finding stays pinned.
    new, stale, _ = compare([Finding("THRD", "a.py", 99, "msg-a")], entries[:1])
    assert not new and not stale


# -- driver + shim ----------------------------------------------------------


def run_cli(*args):
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, capture_output=True, text=True, timeout=300
    )


def test_driver_exits_zero_on_tree():
    proc = run_cli("-m", "scripts.analyze")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_lint_shim_still_works():
    proc = run_cli("scripts/lint.py")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "analyze:" in proc.stdout


def test_driver_rule_filter_and_json_output():
    proc = run_cli("-m", "scripts.analyze", "--rule", "THRD", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert {"files", "findings", "new", "stale", "elapsed_s", "budget_s", "changed_only", "modelcheck", "jitc"} == set(report)
    assert report["new"] == [] and report["stale"] == []
    assert all(f["rule"] == "THRD" for f in report["findings"])
    assert all(f["baselined"] for f in report["findings"])
    assert report["modelcheck"] == {}  # MODL did not run under --rule THRD
    assert report["jitc"] == {}  # JITC did not run under --rule THRD


def test_driver_rejects_unknown_rule():
    proc = run_cli("-m", "scripts.analyze", "--rule", "NOPE")
    assert proc.returncode == 2
    assert "unknown rule" in proc.stderr


def test_driver_list_rules_covers_every_pass():
    proc = run_cli("-m", "scripts.analyze", "--list-rules")
    assert proc.returncode == 0
    for p in PASSES:
        for code in p.CODES:
            assert code in proc.stdout


def test_every_pass_declares_file_scoped():
    for p in PASSES:
        assert isinstance(getattr(p, "FILE_SCOPED", None), bool), p.__name__
    scoped = file_scoped_codes()
    # Cross-file rules must stay OUT of the --changed-only fast path: a
    # partial context would call a changed module's exports dead (DEAD) or
    # one taxonomy side missing (EXCP).
    assert "DEAD" not in scoped and "EXCP" not in scoped
    assert {"E999", "W291", "F401", "SHPE", "THRD", "DTRM"} <= scoped


def test_changed_paths_reads_git_status(tmp_path):
    import os
    import subprocess as sp

    repo = tmp_path / "r"
    repo.mkdir()
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t", "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}
    sp.run(["git", "init", "-q"], cwd=repo, check=True, env=env)
    (repo / "clean.py").write_text("x = 1\n")
    (repo / "dirty.py").write_text("x = 1\n")
    sp.run(["git", "add", "-A"], cwd=repo, check=True, env=env)
    sp.run(["git", "commit", "-qm", "seed"], cwd=repo, check=True, env=env)
    (repo / "dirty.py").write_text("x = 2\n")  # unstaged modification
    (repo / "fresh.py").write_text("y = 1\n")  # untracked
    (repo / "notes.txt").write_text("ignored extension\n")
    assert changed_paths(repo) == ["dirty.py", "fresh.py"]


def test_driver_changed_only_fast_path_exits_zero():
    proc = run_cli("-m", "scripts.analyze", "--changed-only")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "changed-only" in proc.stdout or "0 changed files" in proc.stdout


def test_lint_shim_supports_changed_only():
    proc = run_cli("scripts/lint.py", "--changed-only")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_driver_budget_assertion():
    # An impossible budget must fail loudly.  The real 5 s budget is a wall
    # clock, so `make analyze` enforces it on its own, not a test run that
    # shares the machine with other workers.
    proc = run_cli("-m", "scripts.analyze", "--rule", "W291", "--budget", "0.000001")
    assert proc.returncode == 1
    assert "BUDGET EXCEEDED" in proc.stderr


def test_driver_json_out_artifact(tmp_path):
    out = tmp_path / "report.json"
    proc = run_cli("-m", "scripts.analyze", "--rule", "SHPE", "--json-out", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["new"] == [] and report["stale"] == []
    assert isinstance(report["elapsed_s"], float)
    # the human summary still prints alongside the artifact
    assert "analyze:" in proc.stdout


# -- regression tests for the violations the suite surfaced -----------------


def test_flight_recorder_seen_is_atomic():
    """The pre-THRD ``seen`` probed membership under the lock, released it,
    then recorded — two racing threads could both miss the probe and
    double-record ``seen-pending``.  Now probe + append share one hold."""
    from tpu_scheduler.utils.events import FlightRecorder

    rec = FlightRecorder(max_pods=64)
    barrier = threading.Barrier(8)

    def hammer():
        barrier.wait()
        for _ in range(200):
            rec.seen("default/racer", 1)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tl = rec.timeline("default/racer")
    assert len(tl) == 1 and tl[0]["kind"] == "seen-pending"
    # And the single-threaded contract is unchanged: seen is once-only.
    rec.seen("default/racer", 2)
    assert len(rec.timeline("default/racer")) == 1


def test_shpe_fused_filter_transposed_operand_caught():
    """ISSUE 9 satellite: mutation-check a fused-filter contract —
    transposing the spread-domain selection operand in
    _project_spread_domains ([D, C] fed as [C, D]) must contradict the
    declared `# shape:` contract via the matmul inner-dim check."""
    path = ROOT / "tpu_scheduler" / "ops" / "constraints.py"
    text = path.read_text()
    ctx = make_ctx(("tpu_scheduler/ops/constraints.py", text))
    assert not rule_hits(shapes.run(ctx), "SHPE")
    mutated = text.replace(
        "return nd @ sel, uses_sp @ sel, sp0 @ sel",
        "return nd @ sel, uses_sp @ sel.T, sp0 @ sel",
    )
    assert mutated != text, "the spread-domain projection went missing from constraints.py"
    hits = rule_hits(shapes.run(make_ctx(("tpu_scheduler/ops/constraints.py", mutated))), "SHPE")
    assert len(hits) == 1, "; ".join(h.render() for h in hits)
    assert "matmul inner dims differ" in hits[0].message and "[C, D]" in hits[0].message


def test_shpe_rebalance_fit_matrix_broadcast_caught():
    """ISSUE 11 satellite: mutation-check a rebalance/ contract — dropping
    the column-keeping subscript on the migration-diff operand in
    _fit_matrix (comparing the [N] budget column against the [1, M] victim
    row) must contradict the declared `# shape:` contract via the
    broadcast check."""
    path = ROOT / "tpu_scheduler" / "rebalance" / "solver.py"
    text = path.read_text()
    ctx = make_ctx(("tpu_scheduler/rebalance/solver.py", text))
    assert not rule_hits(shapes.run(ctx), "SHPE")
    mutated = text.replace(
        "budget[:, 0:1] >= req_cpu[None, :]",
        "budget[:, 0] >= req_cpu[None, :]",
    )
    assert mutated != text, "the fit matrix went missing from rebalance/solver.py"
    hits = rule_hits(shapes.run(make_ctx(("tpu_scheduler/rebalance/solver.py", mutated))), "SHPE")
    assert hits, "broadcast-conflicting fit matrix not caught"
    assert any("[N]" in h.message and "[1, M]" in h.message for h in hits), "; ".join(
        h.render() for h in hits
    )


def test_shpe_delta_candidate_mask_broadcast_caught():
    """ISSUE 10 satellite: mutation-check a delta/ contract — dropping the
    per-axis subscript on the min-request operand in _candidate_mask
    (comparing the [N] node column against the whole [R] vector) must
    contradict the declared `# shape:` contract via the broadcast check."""
    path = ROOT / "tpu_scheduler" / "delta" / "repack.py"
    text = path.read_text()
    ctx = make_ctx(("tpu_scheduler/delta/repack.py", text))
    assert not rule_hits(shapes.run(ctx), "SHPE")
    mutated = text.replace(
        "return valid & (avail[:, 0] >= min_req[0]) & (avail[:, 1] >= min_req[1])",
        "return valid & (avail[:, 0] >= min_req) & (avail[:, 1] >= min_req[1])",
    )
    assert mutated != text, "the candidate mask went missing from delta/repack.py"
    hits = rule_hits(shapes.run(make_ctx(("tpu_scheduler/delta/repack.py", mutated))), "SHPE")
    assert hits, "transposed/broadcast-conflicting candidate mask not caught"
    assert any("[N]" in h.message and "[R]" in h.message for h in hits), "; ".join(
        h.render() for h in hits
    )


# -- PROT protocol contracts + MODL model checking ---------------------------

from scripts.analyze import modelcheck, protocol  # noqa: E402

PROT_SYNTH = '''STATES = ("idle", "running", "done")


# protocol: machine widget field=state states=STATES init=idle
# protocol: idle -> running
# protocol: running -> done
# protocol: var work: 0..1 = 0
# protocol: action start: idle -> running effect work = 1
# protocol: action finish: running -> done effect work = 0
# protocol: invariant done-clean: state == done implies work == 0
class Widget:
    def __init__(self):
        self.state = "idle"

    def start(self):
        if self.state == "idle":
            self.state = "running"

    def finish(self):
        if self.state == "running":
            self.state = "done"
'''


def test_prot_clean_synthetic_machine_and_transition_mutations():
    ctx = make_ctx(("tpu_scheduler/w.py", PROT_SYNTH))
    assert not rule_hits(protocol.run(ctx), "PROT")
    # TP 1: an undeclared transition (done -> running restart).
    mutated = PROT_SYNTH.replace(
        'if self.state == "running":\n            self.state = "done"',
        'if self.state == "running":\n            self.state = "done"\n'
        '        elif self.state == "done":\n            self.state = "running"',
    )
    assert mutated != PROT_SYNTH
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/w.py", mutated))), "PROT")
    assert len(hits) == 1 and "undeclared transition done -> running" in hits[0].message
    # TP 2: a state name outside the closed vocabulary.
    mutated = PROT_SYNTH.replace('self.state = "done"', 'self.state = "finished"')
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/w.py", mutated))), "PROT")
    # the typo is flagged AND 'done' loses its only mention (coverage).
    assert any("'finished' is not a declared state" in h.message for h in hits)
    assert any("state 'done'" in h.message and "never used" in h.message for h in hits)
    # TP 3: __init__ drift against init=.
    mutated = PROT_SYNTH.replace('self.state = "idle"', 'self.state = "running"')
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/w.py", mutated))), "PROT")
    assert len(hits) == 1 and "__init__ sets 'running' but init=idle" in hits[0].message


def test_prot_sink_and_accessor_resolution():
    """The breaker shape: writes routed through a sink method and compares
    through an accessor alias are still transition-checked — no special
    cases, the promotion is simply a declared edge."""
    code = '''# protocol: machine m field=state init=a
# protocol: states: a | b | c
# protocol: a -> b
# protocol: b -> c
# protocol: action go: a -> b
# protocol: action fin: b -> c
# protocol: invariant vacuous: state != a or state == a
class M:
    def __init__(self):
        self.state = "a"

    def mode(self):
        if self.state == "a":
            return self.state
        return self.state

    def _transition(self, to):
        self.state = to

    def is_done(self):
        return self.state == "c"

    def poke(self):
        st = self.mode()
        if st == "a":
            self._transition("b")
'''
    # Clean ONLY because the accessor alias narrows the sink call's
    # from-set to {a}: un-narrowed, c -> b would be an undeclared edge.
    ctx = make_ctx(("tpu_scheduler/m.py", code))
    assert not rule_hits(protocol.run(ctx), "PROT")
    # Guarding the same sink call on the wrong branch is caught.
    bad = code.replace('if st == "a":', 'if st == "c":')
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/m.py", bad))), "PROT")
    assert len(hits) == 1 and "undeclared transition c -> b" in hits[0].message
    # And so is removing the guard entirely (the from-set widens to all).
    bad = code.replace('        st = self.mode()\n        if st == "a":\n            self._transition("b")',
                       '        self._transition("b")')
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/m.py", bad))), "PROT")
    assert len(hits) == 1 and "undeclared transition c -> b" in hits[0].message


def test_prot_seeded_provider_resurrect_caught_exactly_once():
    """ISSUE 18 satellite: the canonical seeded bug — a deleted->ready
    resurrect method in provider.py — must produce exactly one PROT
    finding naming the undeclared transition."""
    path = ROOT / "tpu_scheduler" / "autoscale" / "provider.py"
    text = path.read_text()
    rel = "tpu_scheduler/autoscale/provider.py"
    assert not rule_hits(protocol.run(make_ctx((rel, text))), "PROT")
    mutated = text.replace(
        "    def _kill(self, rec: dict, out: dict) -> bool:",
        '    def _resurrect(self, rec: dict) -> None:\n'
        '        if rec["state"] == "deleted":\n'
        '            rec["state"] = "ready"\n'
        "\n"
        "    def _kill(self, rec: dict, out: dict) -> bool:",
    )
    assert mutated != text, "_kill went missing from provider.py"
    hits = rule_hits(protocol.run(make_ctx((rel, mutated))), "PROT")
    assert len(hits) == 1, "; ".join(h.render() for h in hits)
    assert "undeclared transition deleted -> ready" in hits[0].message


def test_prot_keyed_counter_coverage_both_directions():
    """The RESERVATION_STATES exhaustiveness gate: a counts[] key outside
    the vocabulary is flagged, and dropping the only `expired` bump makes
    the member uncovered (the hand-maintained-in-parallel drift class)."""
    path = ROOT / "tpu_scheduler" / "fleet" / "reservation.py"
    text = path.read_text()
    rel = "tpu_scheduler/fleet/reservation.py"
    assert not rule_hits(protocol.run(make_ctx((rel, text))), "PROT")
    # Direction 1: a key the vocabulary does not declare.
    mutated = text.replace('self.counts["committed"] += 1', 'self.counts["comitted"] += 1')
    assert mutated != text
    hits = rule_hits(protocol.run(make_ctx((rel, mutated))), "PROT")
    assert any("'comitted' is not a declared state" in h.message for h in hits)
    assert any("state 'committed'" in h.message and "never used" in h.message for h in hits)
    # Direction 2: a declared member the class never touches.
    mutated = text.replace('self.counts["expired"] += 1', "pass")
    assert mutated != text
    hits = rule_hits(protocol.run(make_ctx((rel, mutated))), "PROT")
    assert len(hits) == 1 and "state 'expired'" in hits[0].message and "never used" in hits[0].message


def test_prot_taxonomy_membership_and_coverage(tmp_path):
    decl = '''# protocol: taxonomy REASONS producers=_skip scope=pkg
REASONS = ("alpha", "beta")
'''
    user_ok = '''class C:
    def _skip(self, reason):
        pass

    def f(self):
        self._skip("alpha")
        self._skip("beta")
        self._skip("beta" if self.x else "alpha")
'''
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "decl.py").write_text(decl)
    (pkg / "user.py").write_text(user_ok)

    def tax_ctx(decl_text, user_text):
        files = []
        for rel, code in (("pkg/decl.py", decl_text), ("pkg/user.py", user_text)):
            (tmp_path / rel).write_text(code)
            files.append(
                SourceFile(path=tmp_path / rel, rel=rel, text=code, lines=code.splitlines(), tree=ast.parse(code))
            )
        return Context(files=files, root=tmp_path, readme="")

    assert not rule_hits(protocol.run(tax_ctx(decl, user_ok)), "PROT")
    # Membership: a produced literal outside the tuple (IfExp branch too).
    bad = user_ok.replace('"beta" if self.x else "alpha"', '"gamma" if self.x else "alpha"')
    hits = rule_hits(protocol.run(tax_ctx(decl, bad)), "PROT")
    assert len(hits) == 1 and "'gamma'" in hits[0].message and "REASONS" in hits[0].message
    # Coverage: a member no producer ever emits (scope fully loaded).
    bad = user_ok.replace('self._skip("beta")\n        self._skip("beta" if self.x else "alpha")', "pass")
    assert bad != user_ok
    hits = rule_hits(protocol.run(tax_ctx(decl, bad)), "PROT")
    assert len(hits) == 1 and "member 'beta' is never produced" in hits[0].message


def test_prot_taxonomy_coverage_silent_on_partial_context(tmp_path):
    """--changed-only soundness: with part of the scope missing from the
    context, the coverage direction must stay silent, not lie."""
    decl = '''# protocol: taxonomy REASONS producers=_skip scope=pkg
REASONS = ("alpha", "beta")
'''
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "decl.py").write_text(decl)
    (pkg / "user.py").write_text("def f(_skip):\n    _skip('alpha')\n")
    files = [
        SourceFile(
            path=pkg / "decl.py", rel="pkg/decl.py", text=decl, lines=decl.splitlines(), tree=ast.parse(decl)
        )
    ]
    ctx = Context(files=files, root=tmp_path, readme="")
    assert not rule_hits(protocol.run(ctx), "PROT")


def test_prot_spec_errors_are_findings():
    bad = '''# protocol: machine m field=state init=a
# protocol: states: a | b
# protocol: a -> b
# protocol: action go: a -> c
# protocol: invariant x: bogus ~ 3
class M:
    def __init__(self):
        self.state = "a"
'''
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/m.py", bad))), "PROT")
    msgs = "; ".join(h.message for h in hits)
    assert "unknown state 'c'" in msgs and "bad condition atom" in msgs
    # And an action edge outside the declared relation is spec-inconsistent.
    bad2 = bad.replace("action go: a -> c", "action go: b -> a").replace("invariant x: bogus ~ 3", "invariant x: state == a")
    hits = rule_hits(protocol.run(make_ctx(("tpu_scheduler/m.py", bad2))), "PROT")
    assert any("undeclared transition b -> a" in h.message for h in hits)


def test_prot_real_tree_is_clean_with_all_six_sites():
    """FP guard over the real annotated tree (the acceptance bar): all six
    protocol sites parse, all three taxonomies parse, zero findings."""
    files = load_files(DEFAULT_PATHS)
    ctx = Context(files=files, root=ROOT, readme="")
    machines, taxes = [], []
    for f in ctx.parsed():
        specs, _ = protocol.collect_machines(f)
        machines.extend(s for s, _cls in specs)
        tx, _ = protocol.collect_taxonomies(f)
        taxes.extend(tx)
    assert {m.name for m in machines} >= {
        "circuit-breaker", "shard-lease", "gang-reservation",
        "drain-migration", "provider-node", "placement-ledger", "fuzz-plan",
    }
    assert len(taxes) >= 3
    hits = rule_hits(protocol.run(ctx), "PROT")
    assert not hits, "; ".join(h.render() for h in hits)


def _machine_from(rel, mutated_text):
    sf = SourceFile(
        path=ROOT / rel, rel=rel, text=mutated_text, lines=mutated_text.splitlines(), tree=ast.parse(mutated_text)
    )
    machines, errs = protocol.collect_machines(sf)
    assert not errs, "; ".join(e.render() for e in errs)
    assert len(machines) == 1
    return machines[0][0]


def _mutate_and_check(rel, old, new, prop):
    """Apply one contract mutation, model-check, and return the single
    violation of ``prop`` (asserting it is reported exactly once)."""
    text = (ROOT / rel).read_text()
    mutated = text.replace(old, new)
    assert mutated != text, f"contract line went missing from {rel}: {old!r}"
    clean = modelcheck.explore(_machine_from(rel, text))
    assert clean["violations"] == [], f"{rel} spec no longer verifies clean"
    result = modelcheck.explore(_machine_from(rel, mutated))
    hits = [v for v in result["violations"] if v[1] == prop]
    assert len(hits) == 1, f"{prop}: {result['violations']}"
    return hits[0]


def test_modl_breaker_double_bind_mutation_caught_once():
    """Dropping the overlay latch from defer lets the deferred pod place
    twice — the assumed-overlay double-bind the invariant exists for."""
    kind, name, trace, _ = _mutate_and_check(
        "tpu_scheduler/runtime/resilience.py",
        "# protocol: action defer: open -> open requires pending == 1 and overlaid == 0 effect overlaid = 1, placed += 1",
        "# protocol: action defer: open -> open requires pending == 1 effect placed += 1",
        "no-double-bind",
    )
    assert kind == "invariant" and trace and trace.count("defer") >= 2


def test_modl_lease_release_is_final_mutation_caught_once():
    """Un-guarding the stale renew thread resurrects the PR-7 race: a
    voluntarily released lease gets re-acquired by the dead thread."""
    kind, name, trace, _ = _mutate_and_check(
        "tpu_scheduler/runtime/shards.py",
        "# protocol: env thread-renew: free -> held requires released == 0",
        "# protocol: env thread-renew: free -> held",
        "release-is-final",
    )
    assert kind == "invariant" and trace == ["acquire", "release", "thread-renew"]


def test_modl_drain_orphan_mutation_caught_once():
    """Breaking unbind's atomic CAS (bound cleared without pending set)
    orphans the victim immediately — a one-step violating trace."""
    kind, name, trace, _ = _mutate_and_check(
        "tpu_scheduler/rebalance/executor.py",
        "# protocol: action unbind: verify -> unbound requires bound == 1 effect bound = 0, pending = 1",
        "# protocol: action unbind: verify -> unbound requires bound == 1 effect bound = 0",
        "never-orphaned",
    )
    assert kind == "invariant" and trace == ["unbind"]


def test_modl_provider_delete_over_pod_mutation_caught_once():
    """Un-guarding kill deletes a node still holding a pod; the minimal
    trace walks the full lifecycle to the racing state."""
    kind, name, trace, _ = _mutate_and_check(
        "tpu_scheduler/autoscale/provider.py",
        "# protocol: action kill: reclaiming -> deleted requires pods == 0",
        "# protocol: action kill: reclaiming -> deleted",
        "delete-only-when-empty",
    )
    assert kind == "invariant" and trace == ["join", "bind", "notice", "kill"]


def test_modl_ledger_flush_twice_mutation_caught_once():
    """Giving the duplicated commit delivery a capacity effect breaks
    exactly-once accounting — the two-phase-commit double-consume."""
    kind, name, trace, _ = _mutate_and_check(
        "tpu_scheduler/delta/state.py",
        "# protocol: env dup-commit: committed -> committed",
        "# protocol: env dup-commit: committed -> committed effect used += 1",
        "flush-at-most-once",
    )
    assert kind == "invariant" and trace == ["commit", "dup-commit"]


def test_modl_trace_minimality_on_seeded_two_phase_commit_bug():
    """ISSUE 18 satellite: the trace-minimality contract.  Seeding the
    two-phase reservation protocol with a TTL that forgets to reclaim the
    peer leases must produce the MINIMAL trace — crash then ttl, exactly
    two environment steps, nothing extra prepended or interleaved."""
    kind, name, trace, _ = _mutate_and_check(
        "tpu_scheduler/fleet/reservation.py",
        "# protocol: env ttl: reserved -> expired requires alive == 0 effect leases = 0",
        "# protocol: env ttl: reserved -> expired requires alive == 0",
        "expired-clean",
    )
    assert kind == "invariant"
    assert trace == ["crash", "ttl"], f"non-minimal or non-deterministic trace: {trace}"


def test_modl_progress_violation_and_state_space_cap():
    # A machine whose declared-stuck state trips the progress property.
    stuck = '''# protocol: machine m field=- init=a
# protocol: states: a | b
# protocol: a -> b
# protocol: action go: a -> b
# protocol: progress alive: state == b
class M:
    pass
'''
    hits = rule_hits(modelcheck.run(make_ctx(("tpu_scheduler/m.py", stuck))), "MODL")
    assert len(hits) == 1 and "progress 'alive' stuck" in hits[0].message and "go" in hits[0].message
    # A runaway var blows the composite-space cap loudly, never hangs.
    runaway = '''# protocol: machine m field=- init=a
# protocol: states: a | b
# protocol: a -> b
# protocol: var x: 0..99999 = 0
# protocol: action inc: * -> * effect x += 1
# protocol: invariant fine: x >= 0
class M:
    pass
'''
    hits = rule_hits(modelcheck.run(make_ctx(("tpu_scheduler/m.py", runaway))), "MODL")
    assert len(hits) == 1 and "exceeds" in hits[0].message


def test_modl_real_tree_verifies_and_exports_stats():
    """The acceptance bar: every committed spec verifies against its
    environment, and LAST_STATS carries the per-machine evidence the
    driver folds into --json-out."""
    files = load_files(DEFAULT_PATHS)
    ctx = Context(files=files, root=ROOT, readme="")
    hits = rule_hits(modelcheck.run(ctx), "MODL")
    assert not hits, "; ".join(h.render() for h in hits)
    stats = modelcheck.LAST_STATS
    assert len(stats) >= 6
    for name, row in stats.items():
        assert row["states"] >= 2, f"{name} explores a vacuous space"
        assert row["violations"] == 0
        assert row["invariants"] + row["progress"] >= 1, f"{name} proves nothing"


def test_driver_json_out_carries_modelcheck_stats(tmp_path):
    out = tmp_path / "report.json"
    proc = run_cli("-m", "scripts.analyze", "--rule", "MODL", "--json-out", str(out), "--no-baseline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert len(report["modelcheck"]) >= 6
    assert all(m["violations"] == 0 for m in report["modelcheck"].values())


def test_prot_and_modl_are_registered_and_scoped():
    codes = all_codes()
    assert "PROT" in codes and "MODL" in codes
    # PROT rides --changed-only; MODL is full-context like EXCP.
    scoped = file_scoped_codes()
    assert "PROT" in scoped and "MODL" not in scoped


# -- JITC compile-cache boundedness + XFER host-sync discipline ---------------

from scripts.analyze import jitc  # noqa: E402


def test_jitc_pack_unbucket_mutation_caught_once():
    """ISSUE 20 acceptance: deleting one power-of-2 round-up under a real
    `# bucket:` contract in pack.py must produce EXACTLY one JITC finding
    (a raw per-cycle dim reaching the jit roots), and the committed file
    must be clean."""
    path = ROOT / "tpu_scheduler" / "ops" / "pack.py"
    text = path.read_text()
    ctx = make_ctx(("tpu_scheduler/ops/pack.py", text))
    assert not rule_hits(jitc.run(ctx), "JITC")
    mutated = text.replace("n_pad = round_up(n_real, node_block)", "n_pad = n_real")
    assert mutated != text, "the node-pad round-up went missing from pack_snapshot"
    hits = rule_hits(jitc.run(make_ctx(("tpu_scheduler/ops/pack.py", mutated))), "JITC")
    assert len(hits) == 1 and "n_pad" in hits[0].message and "raw per-cycle value" in hits[0].message


JITC_ROOT_BRANCH = '''from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n_pad",))
def solve(req, n_pad, limit):
    if limit > 0:
        return jnp.sum(req[:n_pad])
    return jnp.sum(req)
'''


def test_jitc_traced_scalar_branch_caught_and_static_guard():
    """A Python branch on a per-call scalar inside a jit root retraces per
    value (or crashes on a traced array); promoting the name to
    static_argnames is the sanctioned spelling and must silence it."""
    ctx = make_ctx(("tpu_scheduler/ops/fixture.py", JITC_ROOT_BRANCH))
    hits = rule_hits(jitc.run(ctx), "JITC")
    assert len(hits) == 1 and "'limit'" in hits[0].message and "static_argnames" in hits[0].message
    fixed = JITC_ROOT_BRANCH.replace('static_argnames=("n_pad",)', 'static_argnames=("n_pad", "limit")')
    assert not rule_hits(jitc.run(make_ctx(("tpu_scheduler/ops/fixture.py", fixed))), "JITC")


XFER_HOTPATH = '''from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n_pad",))
def solve(req, n_pad):
    return jnp.sum(req)


# hotpath: cycle-driver
def run_cycle(req):
    out = solve(req, n_pad=8)
    return out.item()
'''


def test_xfer_hotpath_item_sync_caught_and_declared_span_guard():
    """`.item()` on a jit-root result inside a `# hotpath:` function is a
    hidden per-cycle device round-trip; both sanctioned spellings — a
    `with span("...host-sync...")` block and a trailing `# host-sync:`
    justification — must silence it."""
    ctx = make_ctx(("tpu_scheduler/ops/fixture.py", XFER_HOTPATH))
    hits = rule_hits(jitc.run(ctx), "XFER")
    assert len(hits) == 1 and ".item()" in hits[0].message and "host-sync" in hits[0].message
    justified = XFER_HOTPATH.replace("return out.item()", "return out.item()  # host-sync: verdict fetch")
    assert not rule_hits(jitc.run(make_ctx(("tpu_scheduler/ops/fixture.py", justified))), "XFER")
    spanned = XFER_HOTPATH.replace(
        "    return out.item()",
        '    with span("solve/host-sync"):\n        return out.item()',
    )
    assert not rule_hits(jitc.run(make_ctx(("tpu_scheduler/ops/fixture.py", spanned))), "XFER")


def test_jitc_real_tree_is_clean_and_exports_stats():
    """FP guard over the real annotated tree: every committed `# bucket:`
    and `# hotpath:` contract must interpret clean, and LAST_STATS carries
    the coverage evidence the driver folds into --json-out."""
    files = load_files(DEFAULT_PATHS)
    ctx = Context(files=files, root=ROOT, readme="")
    n_bucket = sum(f.text.count("# bucket:") for f in files)
    n_hot = sum(f.text.count("# hotpath:") for f in files)
    assert n_bucket >= 9 and n_hot >= 4, "bucket/hotpath annotations went missing"
    hits = [f for f in jitc.run(ctx) if f.rule in ("JITC", "XFER")]
    assert not hits, "; ".join(h.render() for h in hits)
    stats = jitc.LAST_STATS
    assert stats["bucket_contracts"] >= 9
    assert stats["hotpath_contracts"] >= 4
    assert stats["jit_roots"] >= 4
    assert stats["root_call_sites"] >= 5
    assert stats["allowed_syncs"] >= 1


def test_driver_json_out_carries_jitc_stats(tmp_path):
    out = tmp_path / "report.json"
    proc = run_cli("-m", "scripts.analyze", "--rule", "JITC,XFER", "--json-out", str(out), "--no-baseline")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["jitc"]["bucket_contracts"] >= 9
    assert report["jitc"]["jit_roots"] >= 4


def test_jitc_and_xfer_are_registered_and_scoped():
    codes = all_codes()
    assert "JITC" in codes and "XFER" in codes
    # Both interpret per-module with unresolved imports trusted, so they
    # soundly ride the --changed-only fast path.
    scoped = file_scoped_codes()
    assert "JITC" in scoped and "XFER" in scoped
