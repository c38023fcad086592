"""The size at which the tests drive a configuration on the CPU: the
``small`` of its JSON, a dict of the sizes it overrides.  Every test that
runs a cell or a reference takes its size from here, so a configuration
joins the tests by its own files and its entry in ``BENCHMARK.json``."""
from bench import spec as bspec


def small(config: str) -> dict:
    """The configuration's CPU test size (``test_spec.py`` checks that
    every configuration has one that its module builds a program at)."""
    cfg, _ = bspec.load_config(config)
    return dict(cfg["small"])
