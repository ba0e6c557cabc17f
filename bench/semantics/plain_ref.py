"""The plain semantics' reference entry: ``bench/reference.py`` under the
trust threshold of the settings.  No lane draws, so ``seed`` is unused."""

import reference


def makespans(times, kinds, n_events, periods, sc, settings, seed,
              ftype=float, workers=1):
    return reference.makespans(times, kinds, n_events, periods,
                               settings["threshold"], sc, ftype=ftype,
                               workers=workers)
