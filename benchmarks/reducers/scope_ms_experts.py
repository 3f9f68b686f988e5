"""``scope_ms``, for the scope metrics of the expert layer and of latent
attention (PR 31).

``tests/bench_yardstick/test_ouro_yardstick.py`` pins the set of metric
files that name ``scope_ms_later`` to PR 27's four, and that file is not a
later PR's to edit; so these name this module: the same function, and a
test of their own (``test_kanana2_yardstick.py``) that lists its metrics by
name, so that a later scope metric may name this module too.
"""

from .scope_ms import reduce  # noqa: F401
