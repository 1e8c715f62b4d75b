"""Per-layer reducer for a span's self time: its wall less the part its
child spans cover (``choosing-metrics`` section 4), as a share of the
window.  Same contract as ``reducers/host.py``: the metric file's ``params``
and the run's observations in, a number or ``None`` (nothing to read) out."""
from __future__ import annotations


def span_self_share_of_window(params: dict, obs: dict):
    """(``wall_timers[span]`` - sum of ``wall_timers[child]`` over
    ``children``) / window x 100.  The children must run inside the span, on
    its thread, and not inside one another (``wall_timers`` hold one union a
    name, so that is what makes the subtraction a self time).  ``None`` when
    the program recorded no such span, as a program from before the span
    existed does not."""
    w = obs["snapshot"]["wall_timers"]
    whole = w.get(params["span"])
    if not whole:
        return None
    inside = sum(w.get(name, 0.0) for name in params["children"])
    return 100.0 * max(0.0, whole - inside) / obs["window_s"]
