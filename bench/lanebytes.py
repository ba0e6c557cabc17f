"""Bytes the lane loop's work needs, from its inputs and outputs alone.

For each lane, the events consumed are the events of its trace dated
before the lane's makespan (``searchsorted``).  Each consumed event costs
one read of the event (its date, float64, and its kind, one byte) and one
read and one write of the lane state that the schedule's semantics need.
That state is fixed here, whatever implements the loop: seven float64
values (the clock, work done, work saved, the period's start, the current
phase's end, the work left in the period, the next deferred fault's date)
and two int32 values (the trace cursor and the phase).  float64 counts as
8 bytes, so the count is the same for float32 pairs, integer ticks or a
kernel.  The lane loop is bound by bytes, not operations: this is the only
roofline term.
"""

from __future__ import annotations

import numpy as np

EVENT_BYTES = 8 + 1
STATE_BYTES = 7 * 8 + 2 * 4
BYTES_PER_EVENT = EVENT_BYTES + 2 * STATE_BYTES


def events_consumed(times: np.ndarray, lane_trace: np.ndarray,
                    makespans: np.ndarray) -> np.ndarray:
    """Per lane, the events of its trace dated before its makespan."""
    lane_trace = np.asarray(lane_trace)
    makespans = np.asarray(makespans, dtype=np.float64)
    out = np.zeros(lane_trace.size, dtype=np.int64)
    for tr in np.unique(lane_trace):
        sel = lane_trace == tr
        out[sel] = np.searchsorted(times[tr], makespans[sel], side="left")
    return out


def lane_loop_bytes(times: np.ndarray, lane_trace, makespans) -> float:
    return float(events_consumed(times, lane_trace, makespans).sum()
                 * BYTES_PER_EVENT)
