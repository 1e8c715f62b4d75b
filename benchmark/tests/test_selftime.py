"""The self-time reducer on synthetic wall timers (never of the chip).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reducers import selftime  # noqa: E402

PARAMS = {"span": "cli.main_wall", "children": ["plan.execute_wall"]}


def _obs(wall_timers: dict, window_s: float = 10.0) -> dict:
    return {"snapshot": {"wall_timers": wall_timers}, "window_s": window_s}


def test_self_share_is_the_span_less_its_children_over_the_window():
    obs = _obs({"cli.main_wall": 9.5, "plan.execute_wall": 9.0})
    assert selftime.span_self_share_of_window(PARAMS, obs) \
        == pytest.approx(5.0)
    two = {"span": "a", "children": ["b", "c"]}
    obs = _obs({"a": 4.0, "b": 1.0, "c": 2.0}, window_s=8.0)
    assert selftime.span_self_share_of_window(two, obs) \
        == pytest.approx(12.5)


def test_a_child_that_never_ran_takes_nothing_away():
    obs = _obs({"cli.main_wall": 2.0})
    assert selftime.span_self_share_of_window(PARAMS, obs) \
        == pytest.approx(20.0)


def test_a_program_without_the_span_reads_nothing_and_does_not_raise():
    # the parent commit of the PR that added cli.main_wall
    assert selftime.span_self_share_of_window(
        PARAMS, _obs({"plan.execute_wall": 9.0})) is None
    assert selftime.span_self_share_of_window(PARAMS, _obs({})) is None


def test_children_wider_than_the_span_clamp_at_zero():
    # clock jitter between two unions must not report a negative share
    obs = _obs({"cli.main_wall": 1.0, "plan.execute_wall": 1.0001})
    assert selftime.span_self_share_of_window(PARAMS, obs) == 0.0
