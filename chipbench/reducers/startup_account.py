"""How the process started, from the PROGRAM's own account of it
(``get_telemetry().startup_report``, docs/telemetry.md "Set-up and the
compile account"): its ``setup/import`` and ``setup/engine`` spans and one
record for every program JAX traced, lowered and compiled or read from the
persistent cache, up to the window's first step.  ``field`` picks one of
the disjoint parts of ``setup_s`` (``engine_state_s`` alone is a part of
another, ``engine_init_s``); a span's seconds are what is left of it after
the programs made inside it, which are counted under their own fields.
None where the program keeps no account (a commit from before it was
added); 0.0, never None, where it keeps one and nothing happened."""

from chipbench.reducers import program_spans

ENGINE_PARTS = ("engine/state", "engine/weights", "engine/pools")

FIELDS = {
    "import_s": lambda r: r["seconds"]["import"],
    "engine_init_s": lambda r: r["seconds"]["engine"],
    "engine_state_s": lambda r: sum(r["seconds"].get(part, 0.0)
                                    for part in ENGINE_PARTS),
    "trace_lower_s": lambda r: r["seconds"]["trace"] + r["seconds"]["lower"],
    "compile_s": lambda r: r["seconds"]["compile"],
    "cache_read_s": lambda r: r["seconds"]["cache_read"],
    "programs": lambda r: r["programs"],
    "repeat_compiles": lambda r: r["repeat_compiles"],
}


def read(run, field):
    tel = program_spans.telemetry()
    if not hasattr(tel, "startup_report"):
        return None
    until_ns = int(run.steps[0]["t0"] * 1e9) if run.steps else None
    return float(FIELDS[field](tel.startup_report(until_ns=until_ns)))
