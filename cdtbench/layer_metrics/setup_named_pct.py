"""Share of ``setup_s`` under a name of the program's set-up ledger
(``cdt_boot_seconds``, ``cdt_weights_seconds``,
``cdt_program_build_seconds``, and the warm-ups' steady calls), read from
the snapshot taken when the window opens; and, into the run's log, the
table a person reads: seconds by phase, the programs with the most build
seconds, and every program the persistent cache did not serve."""

from collections import defaultdict

from cdtbench.readers import total
from cdtbench.server import say, series

BUILD = "cdt_program_build_seconds"
PHASES = ("trace", "lower", "cache_key", "cache_read", "compile", "first_run")
TOP = 10


def read(ctx):
    opened, cell = ctx["opened"], ctx["cell"]
    if not series(opened, BUILD):
        return None
    by_program = defaultdict(lambda: defaultdict(float))
    calls = defaultdict(int)
    for s in series(opened, BUILD):
        labels = s["labels"]
        by_program[labels["program"]][labels["phase"]] += s["sum"]
        if labels["phase"] == "trace":
            calls[labels["program"]] = s["count"]
    outcomes = defaultdict(lambda: defaultdict(int))
    for s in series(opened, "cdt_program_cache_total"):
        outcomes[s["labels"]["program"]][s["labels"]["outcome"]] += int(
            s["value"])
    named = {phase: sum(p[phase] for p in by_program.values())
             for phase in PHASES}
    for s in series(opened, "cdt_boot_seconds"):
        named[f"boot.{s['labels']['phase']}"] = s["value"]
    for s in series(opened, "cdt_weights_seconds"):
        labels = s["labels"]
        named[f"weights.{labels['phase']} {labels['model']}".strip()] = \
            s["sum"]
    named["steady calls of the warm-ups"] = total(
        opened, "cdt_pipeline_execute_seconds", None, "sum", cell)
    whole = sum(named.values())
    setup_s = ctx["setup_s"]

    say(f"set-up ledger: {whole:.2f} s of {setup_s:.2f} s under a name")
    for name, seconds in sorted(named.items(), key=lambda kv: -kv[1]):
        say(f"  {seconds:8.2f} s  {name}")
    say(f"  {setup_s - whole:8.2f} s  under no name")

    def row(program):
        phases = by_program[program]
        cache = ", ".join(f"{k} {v}" for k, v in
                          sorted(outcomes[program].items())) or "-"
        return (f"  {sum(phases.values()):8.2f} s  {program}: "
                + ", ".join(f"{k} {phases[k]:.2f}" for k in PHASES
                            if phases.get(k))
                + f"; traced {calls[program]}x; cache: {cache}")

    ranked = sorted(by_program, key=lambda p: -sum(by_program[p].values()))
    say(f"the {TOP} programs with the most build seconds:")
    for program in ranked[:TOP]:
        say(row(program))
    unserved = [p for p in ranked
                if outcomes[p].get("miss") or outcomes[p].get("uncached")]
    say(f"programs the persistent cache did not serve ({len(unserved)}; "
        "miss = compiled and written, uncached = compiled and not written):")
    for program in unserved:
        say(row(program))
    return 100.0 * whole / setup_s if setup_s else None
