"""``moe_grouped_rows`` — compiled step: rows the held experts' grouped
products are handed, a layer a step: the static size of the buffer of routed
rows in the program traced last before telemetry was armed as set-up ended
(``telemetry.compiles`` counter ``moe_grouped_rows``, noted by
``transformer/hybrid.py``).  None where the program keeps no such counter."""
import compile_counters


def read(run):
    counters = compile_counters.at_armed(run)
    if not counters or not counters.get("moe_grouped_rows"):
        return None
    return float(counters["moe_grouped_rows"])
