"""Helpers the per-layer metric readers share."""


def program_runs(rec, name):
    """Executions of the jitted program ``name`` in the traced window (the
    busiest device's count)."""
    tr = rec.get("trace")
    if not tr:
        return 0
    return max(tr["program_counts"].get(name, {}).values(), default=0)


def program_ms(rec, name):
    """Device milliseconds of program ``name``, averaged over devices."""
    per = rec["trace"]["programs"].get(name, {})
    return 1e3 * sum(per.values()) / max(len(per), 1)
