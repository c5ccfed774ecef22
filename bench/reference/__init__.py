"""Plain references for the benchmark's correctness checks.

Straightforward ``jax.numpy`` in float32, written from the definitions in
arXiv:2101.08763 §III (exemplar clustering, L(S) and f(S) = L({e0}) −
L(S ∪ {e0}) with e0 the all-zero vector) and nothing else: no kernel, no
tiling, no import of the system under test.

Every contraction takes a ``precision``: ``"highest"`` is float32 at
``Precision.HIGHEST``, the precision the configurations state; ``"high"``
is the three-pass bfloat16 product (hi·hi + hi·lo + lo·hi) that
``Precision.HIGH`` stands for on a TPU, written out so that it computes the
same on any backend. ``"high"`` is the control: the reference put in the
program's place one precision step below, which the checks must refuse.
"""
