"""benchmarks/run.py CLI: suite names are validated up front.

The old ``only = sys.argv[1]`` filter silently ran *nothing* on a typo'd
suite name; argparse now rejects unknown names with a hard error."""
import pytest

from benchmarks.run import SUITES, main


def test_unknown_suite_is_hard_error(capsys):
    with pytest.raises(SystemExit) as ei:
        main(["definitely-not-a-suite"])
    assert ei.value.code == 2
    assert "invalid choice" in capsys.readouterr().err


def test_suites_cover_known_sections():
    for s in ("paper", "dse", "pareto", "dse-perf", "faults", "fusion",
              "codegen", "trace", "kernels"):
        assert s in SUITES


def test_help_lists_suites(capsys):
    with pytest.raises(SystemExit) as ei:
        main(["--help"])
    assert ei.value.code == 0
    assert "codegen" in capsys.readouterr().out


def test_failing_phase_fails_the_run(monkeypatch):
    """A phase that raises must fail the run instead of printing
    'unavailable' and exiting 0."""
    from benchmarks import kernel_bench

    def boom(emit):
        raise AttributeError("kernel path broken")

    monkeypatch.setattr(kernel_bench, "run", boom)
    with pytest.raises(AttributeError, match="kernel path broken"):
        main(["kernels"])
