"""device.idle_unspanned_pct: the share of the traced window in which the
first device is idle and no program span is open: the idle time the
program's own spans do not explain (``bench/spans.py``)."""

import spans


def read(run):
    return spans.idle_unspanned_pct(run)
