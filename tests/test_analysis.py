"""PR 6 wavecheck: the static invariant analyzer for the device wave path.

Acceptance: ``run_all()`` reports ZERO violations on an 8-device mesh
(every shipped wave program inside its declared collective budget, fully
donated, recompile-free once warm, int32-overflow-clean, AST-clean), and
the mutation self-test proves a broken Discipline is caught by >= 3
independent rule families.  Plus single-process unit tests for each
analyzer layer (HLO parser, AST lint, overflow taint lint, compile
tracker)."""
import json
import textwrap

from multidev import run_multidev

# ---------------------------------------------------------------------------
# acceptance: the full analyzer on the repo, 8 devices, zero violations
# ---------------------------------------------------------------------------
RUN_ALL = r"""
import json
from repro.analysis import run_all
report = run_all()
print(json.dumps(report))
"""


def test_run_all_zero_violations_8dev():
    report = json.loads(run_multidev(RUN_ALL, n_dev=8).splitlines()[-1])
    assert report["passed"], report["violations"]
    assert report["n_violations"] == 0, report["violations"]
    # every discipline x schedule is present: 4x3 wave programs + legacy
    # step + 4 migrations + 4x2 telemetry-on [obs] twins (PR 7) + 4x2
    # occupancy-bucket [compact] twins at the narrow ladder width (PR 9,
    # L=2 so the ladder is {1, 2} and w=1 is the one narrow rung) plus
    # the 2 runtime-constructed queue twins (PR 10) = 35
    assert len(report["programs"]) == 35, sorted(report["programs"])
    # the [obs] twins lower against the SAME budgets as their off twins
    obs = [n for n in report["programs"] if "[obs]" in n or ",obs]" in n]
    assert len(obs) == 8, sorted(report["programs"])
    # ... and so do the [compact] twins (PR 9): same ≤2-a2a wave contract
    compact = [n for n in report["programs"] if "compact:" in n]
    assert len(compact) == 8, sorted(report["programs"])
    # the budgets are exact on the headline invariant: 2 a2a per wave
    for name, info in report["programs"].items():
        if (name.endswith(".step") or ".step[compact" in name) \
                and "legacy" not in name:
            assert info["collectives"].get("all-to-all") == 2, (name, info)
    legacy = report["programs"]["queue-legacy.step"]
    assert legacy["collectives"].get("all-to-all") == 5, legacy
    for kind in ("queue", "stack", "priority", "seap"):
        mig = report["programs"][f"{kind}.migration"]
        assert mig["collectives"].get("all-to-all") == 1, (kind, mig)
        assert mig["aliases"] >= 2, (kind, mig)
    # the recompile guard actually warmed something, then stayed silent
    rg = report["recompile_guard"]
    assert rg["warm_compiles"] > 0 and rg["second_bounce_compiles"] == 0, rg


SELFTEST = r"""
import json
from repro.analysis.selftest import run_selftest
print(json.dumps(run_selftest()))
"""


def test_mutation_selftest_trips_at_least_three_rules_8dev():
    report = json.loads(run_multidev(SELFTEST, n_dev=8).splitlines()[-1])
    assert report["passed"], report
    assert report["n_tripped"] >= 3, report
    # the broken Discipline itself (extra collective + dropped donation)
    # must be caught — not just the idiom mutations
    assert "collective_budget" in report["tripped_rules"], report
    assert "donation" in report["tripped_rules"], report


# ---------------------------------------------------------------------------
# HLO parser units (pure string handling — no jax)
# ---------------------------------------------------------------------------
_HLO = textwrap.dedent("""\
    HloModule jit_step, is_scheduled=true, \
input_output_alias={ {0}: (0, {}, must-alias), {1}: (1, {}, may-alias) }, \
entry_computation_layout={(s32[8]{0})->s32[8]{0}}

    ENTRY %main (p0: s32[8], p1: s32[8]) -> (s32[8], s32[8]) {
      %p0 = s32[8]{0} parameter(0)
      %p1 = s32[8]{0} parameter(1)
      %a2a.1 = s32[8]{0} all-to-all(s32[8]{0} %p0), replica_groups={}
      %start = (s32[8]{0}, s32[8]{0}) all-to-all-start(s32[8]{0} %p1)
      %done = s32[8]{0} all-to-all-done((s32[8]{0}, s32[8]{0}) %start)
      %cp = s32[8]{0} collective-permute(s32[8]{0} %a2a.1)
      ROOT %t = (s32[8]{0}, s32[8]{0}) tuple(%cp, %done)
    }
""")


def test_hlo_parser_counts_and_aliases():
    from repro.analysis import collective_counts, input_output_aliases
    from repro.analysis.hlo import parse_hlo

    counts = collective_counts(_HLO)
    # async start/done pairs collapse into ONE logical collective
    assert counts["all-to-all"] == 2, counts
    assert counts["collective-permute"] == 1, counts
    aliases = input_output_aliases(_HLO)
    assert len(aliases) == 2, aliases
    assert {a.param for a in aliases} == {0, 1}
    prog = parse_hlo(_HLO)
    assert any(op.opcode == "tuple" for op in prog.ops)


# ---------------------------------------------------------------------------
# AST lint units (pure source handling — no jax)
# ---------------------------------------------------------------------------
def test_astlint_flags_device_scope_sins():
    from repro.analysis import lint_paths
    from repro.analysis.astlint import lint_source

    bad = textwrap.dedent("""
        import jax
        from jax import lax
        def body(c, x):
            k = int(x)
            assert k > 0
            jax.debug.print("occ={}", c)
            return c, x
        def run(c, xs):
            out = lax.scan(body, c, xs)
            while True:
                out[0].block_until_ready()
            return out
    """)
    checks = {v.detail["check"] for v in lint_source(bad, "bad.py")}
    assert checks == {"no-bare-assert", "no-traced-cast",
                      "no-block-in-burst",
                      "no-host-callback-in-wave"}, checks

    # int()/float() OUTSIDE device scope stays legal (host-side code)
    ok = "def host(x):\n    return int(x) + 1\n"
    assert lint_source(ok, "ok.py") == []

    # the sanctioned Wavescope drain is exempt from the callback rule
    sanctioned = textwrap.dedent("""
        def dispatch(self, carry, ops):
            def drain_metrics(m):
                return jax.device_get(m.rows)
            return drain_metrics
    """)
    assert lint_source(sanctioned, "obs.py") == []

    # ... but any other callback nested in a wave method is flagged
    smuggled = textwrap.dedent("""
        def dispatch(self, carry, ops):
            jax.debug.callback(lambda x: None, carry)
            return carry
    """)
    checks = {v.detail["check"] for v in lint_source(smuggled, "bad2.py")}
    assert checks == {"no-host-callback-in-wave"}, checks

    # and the shipped device-path modules are clean
    violations, info = lint_paths()
    assert violations == [], [str(v) for v in violations]
    assert any("wave_engine" in f for f in info["files_checked"])


# ---------------------------------------------------------------------------
# overflow taint lint units (single-device jnp)
# ---------------------------------------------------------------------------
def test_overflow_lint_clean_on_guarded_and_trips_on_naive():
    import jax
    import jax.numpy as jnp

    from repro.analysis import check_int32_overflow
    from repro.analysis.overflow import lint_jaxpr

    sc = jax.ShapeDtypeStruct((), jnp.int32)

    def guarded_mid(lo, hi):
        return (lo & hi) + ((lo ^ hi) >> 1)

    assert lint_jaxpr(guarded_mid, (sc, sc), program="mid",
                      tainted_args=(0, 1)) == []

    def naive_mid(lo, hi):
        return (lo + hi) // 2

    vs = lint_jaxpr(naive_mid, (sc, sc), program="mid",
                    tainted_args=(0, 1))
    assert vs and vs[0].rule == "int32_overflow", vs

    # INF growth is fine when the result feeds a clamp/select guard
    INF = jnp.int32(2 ** 30)

    def clamped(b):
        return jnp.minimum(b + INF, INF)

    assert lint_jaxpr(clamped, (sc,), program="clamped") == []

    # the shipped scan_queue entry points are all clean
    violations, info = check_int32_overflow()
    assert violations == [], [str(v) for v in violations]
    assert info["entries"], info


# ---------------------------------------------------------------------------
# compile tracker unit (single-device)
# ---------------------------------------------------------------------------
def test_compilation_tracker_counts_only_fresh_compiles():
    import jax
    import jax.numpy as jnp

    from repro.analysis import CompilationTracker

    @jax.jit
    def f(x):
        return x * 2 + 1

    x = jnp.arange(7)
    with CompilationTracker() as cold:
        f(x).block_until_ready()
    assert cold.count >= 1, cold.count
    with CompilationTracker() as warm:
        f(x).block_until_ready()          # cache hit: no backend compile
    assert warm.count == 0, warm.count


def test_compilation_tracker_names_the_program_it_compiled():
    """Compiles are kept by program name, for the process and for the
    tracker; reading a dispatched program's phase table back from jax's
    cache compiles nothing."""
    import numpy as np

    from repro.analysis import CompilationTracker
    from repro.dqueue import ElasticDeviceQueue

    q = ElasticDeviceQueue(1, cap=32, payload_width=3, ops_per_shard=4)
    e = np.ones((5, 4), bool)
    enq = np.arange(20).reshape(5, 4) % 2 == 0
    pw = np.zeros((5, 4, 3), np.int32)
    before = CompilationTracker.by_program().get(
        "jit(skueue_fifo_waves)", {"compiles": 0, "compile_s": 0.0})
    with CompilationTracker() as cold:
        q.run_waves(enq, e, pw)
    assert "jit(skueue_fifo_waves)" in cold.programs
    after = CompilationTracker.by_program()["jit(skueue_fifo_waves)"]
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]
    with CompilationTracker() as warm:
        q.run_waves(enq, e, pw)
        tables = q.wave_phases()
    assert warm.count == 0 and warm.programs == []
    assert len(tables) == 1
    assert {"dispatch", "commit", "reply"} <= set(tables[0].values())


def test_budget_check_reports_undeclared_collectives():
    from repro.analysis import CollectiveBudget, check_budget

    text = _HLO
    ok = CollectiveBudget(exact={"all-to-all": 2},
                          max={"collective-permute": 4})
    assert check_budget("p", text, ok) == []
    tight = CollectiveBudget(exact={"all-to-all": 1}, max={})
    vs = check_budget("p", text, tight)
    assert vs, "over-budget a2a and undeclared cp must both be flagged"
    assert len(vs) >= 2, [str(v) for v in vs]
