"""Per-layer reducers over what the host saw: the program's counters, wall
timers, histograms and per-occurrence span durations, and the load
generator's own clock.  Each takes the metric file's ``params`` and the run's
observations, and returns a number or ``None`` (nothing to read)."""
from __future__ import annotations

import math


def _quantile(values, q: float):
    """Nearest-rank quantile of a list (no interpolation: the value was
    observed)."""
    if not values:
        return None
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def counter_ratio(params: dict, obs: dict):
    """sum(numerator counters) / sum(denominator counters) x scale."""
    c = obs["snapshot"]["counters"]
    den = sum(c.get(k, 0) for k in params["denominator"])
    if not den:
        return None
    num = sum(c.get(k, 0) for k in params["numerator"])
    return params.get("scale", 1.0) * num / den


def span_share_of_window(params: dict, obs: dict):
    """Share of the window in which a span was open (``wall_timers`` hold
    the union of overlapping occurrences).  ``spans`` lists alternatives:
    the first that is non-zero is read."""
    w = obs["snapshot"]["wall_timers"]
    for name in params["spans"]:
        if w.get(name):
            return 100.0 * w[name] / obs["window_s"]
    return None


def hist_quantile(params: dict, obs: dict):
    """A summary quantile (``p50`` | ``p95`` | ``p99``) of one of the
    program's histograms."""
    h = obs["snapshot"]["histograms"].get(params["histogram"])
    if not h or not h.get("count"):
        return None
    return params.get("scale", 1.0) * h[params["quantile"]]


def span_quantile(params: dict, obs: dict):
    """A quantile of the per-occurrence durations of one span, from the
    program's own recorder (traced run only)."""
    v = _quantile(obs.get("span_durations", {}).get(params["span"], []),
                  params["quantile"])
    return None if v is None else params.get("scale", 1.0) * v


def generator_lateness(params: dict, obs: dict):
    """A quantile of send instant - due instant of the load generator."""
    v = _quantile(obs.get("lateness_s", []), params["quantile"])
    return None if v is None else params.get("scale", 1.0) * v
