"""Share of the traced window in which the device was idle while the host
was inside the join's driver loop: idle time whose innermost program span
is ``join`` or a ``join.*`` span (expansion count, dispatch, scalar syncs,
pair fetch, fallback, final merge), over the window."""

from chipbench import stages


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    found = stages.of(run)
    if found is None or found["window_s"] <= 0:
        return None
    idle = found["program_idle"]
    if not set(idle) - {stages.NO_SPAN}:
        return None
    loop = sum(t for name, t in idle.items()
               if name == "join" or name.startswith("join."))
    return 100.0 * loop / found["window_s"]
