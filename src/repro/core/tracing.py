"""Names of the program's host spans.

The spans are ``jax.profiler.TraceAnnotation``s, always on: with no
profiler session open one costs about a microsecond, and inside a session
it lands in the same ``.xplane.pb`` as the device's operations, on the
same clock, so a trace says what the host was doing while the device sat
idle (``bench/spans.py`` splits a window's idle time by span). They wrap
host work only, never the body of a jitted function.

- ``evaluate_multiset``: the whole of ``core.evaluator.evaluate_multiset``,
  from entry until it returns the device array.
- ``evaluate_multiset.e0_distances``: the e0 distance column that
  ``evaluate_multiset`` computes when the caller passes no ``d_e0``: the
  dispatch of its one compiled program.
- ``exemplar_eval``: the whole of ``kernels.ops.exemplar_eval``: the tile
  and chunk plan, the slices of each chunk and the dispatch of its jitted
  kernel.
- ``run_selection``: the whole of ``core.engine.run_selection``, every
  strategy and plan, from entry until it returns the ``OptResult``.
- ``run_selection.prepare``: from entry up to the dispatch of the
  selection program: validation, the distinct candidates of the round
  rows, the precision policy and backend, the block size, and the copies
  of the cache seed and the candidate rows to the device.
- ``run_selection.fetch``: the read of the picks, the trajectory and the
  scored count back to the host, which waits for the device to finish
  the selection, and the building of the ``OptResult``.
- ``function.init``: the whole of ``core.functions.ExemplarClustering``'s
  constructor: one dispatch of the e0 distance column with its mean,
  ``L({e0})``, and the host read of the mean.

The evaluator's self time is ``evaluate_multiset`` less its two child
spans: the backend branch and the cast of the output. The engine's
dispatch is ``run_selection`` less its two child spans.

One counter rides the selection's result instead of the trace:

- ``OptResult.rescore_trips``: CELF's re-score trips, the iterations of
  the lazy step's while loop (``core.engine.make_lazy_step``) summed over
  the rounds of a selection; each trip scores up to ``top_b`` candidates.
  The scan emits it per round beside the scored count, and
  ``run_selection.fetch`` reads it back with the picks, so it costs no
  host sync of its own. ``lazy_greedy(mode="host")`` counts its batches
  the same way; the dense and stochastic strategies report 0.
"""

EVALUATE_MULTISET = "evaluate_multiset"
E0_DISTANCES = "evaluate_multiset.e0_distances"
EXEMPLAR_EVAL = "exemplar_eval"
RUN_SELECTION = "run_selection"
RUN_SELECTION_PREPARE = "run_selection.prepare"
RUN_SELECTION_FETCH = "run_selection.fetch"
FUNCTION_INIT = "function.init"

SPANS = (EVALUATE_MULTISET, E0_DISTANCES, EXEMPLAR_EVAL, RUN_SELECTION,
         RUN_SELECTION_PREPARE, RUN_SELECTION_FETCH, FUNCTION_INIT)
