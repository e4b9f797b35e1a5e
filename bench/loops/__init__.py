"""Traffic loops: a traffic mix's ``loop`` key names the module here.

Each module gives:

* ``build(struct, options) -> app`` — the program's constructor (the
  harness times it as ``app_build_s``);
* ``entry(app) -> call`` — the timed path the window drives;
* ``Session(struct, call, traffic, seed)`` with ``warm()``, ``step()``
  (one closed-loop call), ``drain()`` (wait for every call still
  outstanding; the window ends when it returns), ``finish()`` (after the
  window: results to the host, device state dropped), ``metrics(elapsed_s)`` (end-to-end candidates),
  ``counters()`` (program counters for per-layer metrics),
  ``work_bytes()`` (compulsory bytes of the completed calls),
  ``check(limits)`` (``{name: (value, limit)}``) and the counts
  ``attempted``, ``failed`` and ``completed``.
"""
