"""The timelines that the run's ``train_model`` calls left in the program's
flight recorder.

Since PR 39 every ``train_model`` call records one timeline named
``rdp.train.job`` (``observability/recorder.py``; what ``GET /debug/spans``
serves): the call's ``rdp.train.*`` phases as spans on the host's monotonic
clock, each with the span that caused it, its thread's name and its stats
as attributes, and counts at the same boundaries (the root's
``process_age_s``, ``process_jit_s``, ``process_cache_misses``; ``bytes``
on a checkpoint write; ``steps`` and ``routed_rows`` on an epoch). They are
recorded with no profiler session, so they cover set-up, which the traced
window does not.

A reader takes the timelines whose ``checkpoint_dir`` label lies under the
run's work directory, oldest first: the last is the window's call, those
before it are set-up's (the probe and the first epoch). A program that
records none (the parent of that PR) gives ``None``, never an error. A
timeline is the plain dict that ``Timeline.to_dict()`` gives: attribute
values are strings.

What the readers report are readings of the machine a cell is measured on:
its start-up, its compile cache, its disk. Like the device's they come only
from a run on the chip: where the harness was told to require none
(``require_chip=False``, the tests' tiny sizes on the CPU, where
``ctx.peaks`` is ``None``) they give nothing, and a cell's CPU line carries
what it carried before.
"""

from __future__ import annotations

import os
from pathlib import Path

JOB = "rdp.train.job"


def recorded() -> list:
    """Every timeline the process's recorder holds, pinned or recent, as
    dicts, oldest first."""
    from robotic_discovery_platform_tpu.observability.recorder import RECORDER

    snapshot = RECORDER.snapshot()
    by_seq = {t["seq"]: t for t in snapshot["recent"] + snapshot["pinned"]}
    return [by_seq[seq] for seq in sorted(by_seq)]


def calls(ctx, with_setup: bool = False):
    """``(set-up's calls, the window's call)`` of this run, or ``None``
    where the program recorded no call of it or the run required no chip;
    ``with_setup``: also where no call of set-up's came before the
    window's."""
    if ctx.peaks is None:
        return None
    home = Path(os.path.abspath(ctx.cell.workdir))
    mine = [t for t in recorded() if t["name"] == JOB and t["spans"]
            and Path(os.path.abspath(t["labels"].get(
                "checkpoint_dir", os.sep))).is_relative_to(home)]
    if len(mine) < (2 if with_setup else 1):
        return None
    return mine[:-1], mine[-1]


def root(timeline: dict) -> dict:
    return timeline["spans"][0]


def number(span: dict, key: str):
    """An attribute as a number, ``None`` where the span has none."""
    value = span["attributes"].get(key)
    return None if value is None else float(value)


def named(timeline: dict, names, own_thread: bool = False) -> list:
    """The closed spans of these names (a string is one name), on the
    job's own thread alone if asked."""
    names = (names,) if isinstance(names, str) else tuple(names)
    thread = root(timeline)["attributes"].get("thread")
    return [s for s in timeline["spans"]
            if s["name"] in names and s["duration_ms"] is not None
            and (not own_thread or s["attributes"].get("thread") == thread)]


def seconds(spans) -> float:
    return sum(s["duration_ms"] for s in spans) / 1e3
