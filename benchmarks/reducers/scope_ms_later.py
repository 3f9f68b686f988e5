"""``scope_ms``, for a scope metric added after PR 25.

``tests/bench_yardstick/test_scope_metrics.py`` pins, on a hand-made table of
scopes, every metric file whose reducer is named ``scope_ms``; a metric a
later PR adds has no row in that table and that file is not the later PR's
to edit. Such a metric names this module as its reducer: the same function,
and a test of its own beside the file that adds it.
"""

from .scope_ms import reduce  # noqa: F401
