"""What a configuration's numbers mean: one module per semantics.

A configuration names its semantics with ``"semantics": "<name>"``
(``"plain"`` where the key is absent); the harness imports
``semantics.<name>`` (``bench/semantics/<name>.py``) and refuses a name
with no module before JAX starts.  A semantics module supplies:

- ``check(cfg, mix)``: raises ValueError for a scenario field or a trust
  policy that it does not model.
- ``scenario(cfg)``: the derived quantities (seconds) that the harness, the
  strategies and the reference read: at least ``mu``, ``c``, ``d``, ``r``,
  ``cp``, ``time_base`` and ``horizon``.
- ``BANK_SOURCES`` and ``bank(cfg, seed)``: the pool of traces as
  ``(times, kinds, n_events)``, padded with +inf dates, drawn from the
  configuration's ``bank_seed``.  The pool is kept on disk under a key of
  the configuration's JSON and the source of each file of
  ``BANK_SOURCES`` (paths under ``bench/``); a module that draws its own
  bank lists its own file there.  ``traces(cfg, sc, times, kinds)``: the
  program's ``EventTrace`` of each row.
- ``settings(mix, sc)``: the fields that every strategy of a sweep shares,
  as plain data (no program object), and ``strategies(mix, sc, periods)``:
  the program's ``Strategy`` list of one sweep, one per period, in order.
- ``REFERENCE``: the name of a module that imports nothing but numpy and
  the benchmark's own numpy-only modules, so that a spawned worker can
  load it, with the entry ``makespans(times, kinds, n_events, periods, sc,
  settings, seed, ftype=float, workers=1)``: the (len(periods), n_traces)
  makespans.  ``seed`` is the run's; a semantics whose lanes draw rebuilds
  the runner's per-trace stream, ``seed + 7919 * trace index`` in the
  run's trace order.
- ``lane_bytes(times, lane_trace, makespans)``: the bytes of the lanes'
  work that the lane loop's roofline divides.
"""
