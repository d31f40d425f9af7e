"""data.place_ms: mean host milliseconds of the program's ``data.place``
span (``repro.train.data.place_batch`` putting a batch on the mesh), over
every call of the run, traced or not, from the program's span registry
(``repro.analysis.tracing.span_stats``). Nothing to read where the
program keeps no such registry or span."""


def read(run):
    try:
        from repro.analysis.tracing import span_stats
    except ImportError:
        return None
    stats = span_stats().get("data.place")
    if not stats or not stats["count"]:
        return None
    return 1e3 * stats["total_s"] / stats["count"]
