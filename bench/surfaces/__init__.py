"""One driver per surface of the program; a configuration's ``surface``
key names its module here.  A surface module provides:

* ``setup(config, traffic, seed) -> state``: build and warm up everything
  the window uses;
* ``window(state, seconds, marks) -> dict``: drive the program's entry
  point, calling ``marks.start()`` and ``marks.stop()`` around the
  measured part; the dict holds ``seconds``, ``attempted``, ``failed`` and
  what ``end_to_end`` and ``work`` need;
* ``end_to_end(window) -> {metric: value}`` and ``work(state, window)``
  (the work counts that per-layer readers divide by);
* ``check(state, seed) -> [(name, value, limit)]``: the window's output
  against the plain reference, with the traffic's limits;
* ``readings(state, seed, control=None)`` and ``CONTROL``: the same
  numbers without a window, for ``bench/control.py``; a training surface
  also names the faults it can have in ``FAULTS``.
"""
