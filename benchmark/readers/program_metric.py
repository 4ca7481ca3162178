"""A counter or a gauge of the program's own registry
(``mmlspark_tpu/observability/metrics.py``), by name: its value as the run
left it. ``None`` where the registry has none of that name (a program from
before the instrument; a cell whose step never sets it), and looking never
creates one. A gauge holds its newest value: ``moe.rows_moved`` is the
last step's that ``trainer:flush`` fetched, not a window's median."""


def read(rin, name):
    try:
        from mmlspark_tpu.observability import metrics
    except ImportError:
        return None
    found = metrics.get_registry().to_dict().get(name)
    if found is None or "value" not in found:      # none, or a histogram
        return None
    return float(found["value"])
