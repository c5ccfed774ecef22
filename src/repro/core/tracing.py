"""Names of the program's host spans.

The spans are ``jax.profiler.TraceAnnotation``s, always on: with no
profiler session open one costs about a microsecond, and inside a session
it lands in the same ``.xplane.pb`` as the device's operations, on the
same clock, so a trace says what the host was doing while the device sat
idle (``bench/spans.py`` splits a window's idle time by span). They wrap
host work only, never the body of a jitted function.

- ``evaluate_multiset``: the whole of ``core.evaluator.evaluate_multiset``,
  from entry until it returns the device array.
- ``evaluate_multiset.e0_distances``: the eager e0 distance column that
  ``evaluate_multiset`` computes when the caller passes no ``d_e0``.
- ``exemplar_eval``: the whole of ``kernels.ops.exemplar_eval``: the tile
  and chunk plan, the slices of each chunk and the dispatch of its jitted
  kernel.

The evaluator's self time is ``evaluate_multiset`` less its two child
spans: the backend branch and the cast of the output.
"""

EVALUATE_MULTISET = "evaluate_multiset"
E0_DISTANCES = "evaluate_multiset.e0_distances"
EXEMPLAR_EVAL = "exemplar_eval"

SPANS = (EVALUATE_MULTISET, E0_DISTANCES, EXEMPLAR_EVAL)
