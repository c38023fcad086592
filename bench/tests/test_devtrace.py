"""The trace reduction, on hand-made events and on traces recorded on a
TPU v5e (``bench/tests/data``)."""
from pathlib import Path

import pytest

from bench import devtrace, peaks, run
from bench import spec as bspec
from bench.devtrace import Op, Span


def test_reduce_hand_made():
    spans = [Span("bench.stretch", 1000, 1000),
             Span("bench.dispatch", 1000, 120), Span("bench.wait", 1300, 600)]
    ops = [Op(0, "fusion", 1100, 100, False),       # glue
           Op(0, "kernel", 1200, 300, True),        # kernel
           Op(0, "fusion.1", 1450, 100, False),     # overlaps the kernel
           Op(0, "kernel", 1700, 200, True),
           Op(0, "before", 900, 50, False)]         # outside the stretch
    s = devtrace.reduce(ops, spans, n_devices=1)
    assert s.kernel_s == pytest.approx(500e-9)
    assert s.glue_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx(650e-9)  # [1100, 1550) + [1700, 1900)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.device_ops[0] == ["kernel", pytest.approx(500e-9)]
    # gaps: [1000, 1100) in dispatch, [1550, 1700) in wait, [1900, 2000)
    assert s.idle_gaps == [["bench.wait", pytest.approx(150e-9)],
                           ["bench.dispatch", pytest.approx(100e-9)],
                           ["host outside bench spans", pytest.approx(100e-9)]]


def test_idle_self_goes_to_the_innermost_open_span():
    """The idle time by the innermost host span open over it, the
    program's spans among them; the longest gaps are still named by the
    benchmark's spans alone."""
    spans = [Span("bench.stretch", 0, 1000),
             Span("bench.dse", 0, 800), Span("hls.compile", 100, 600),
             Span("hls.dep_ilp", 200, 100), Span("hls.dep_ilp", 400, 50),
             Span("bench.run", 800, 100), Span("hls.lower", 920, 80)]
    ops = [Op(0, "kernel", 850, 50, True)]
    s = devtrace.reduce(ops, spans, n_devices=1)
    # gaps [0, 850) and [900, 1000)
    assert s.idle_gaps == [
        ["bench.dse", pytest.approx(850e-9)],
        [devtrace.OUTSIDE, pytest.approx(100e-9)]]
    assert s.idle_self == [
        ["hls.compile", pytest.approx(450e-9)],
        ["bench.dse", pytest.approx(200e-9)],
        ["hls.dep_ilp", pytest.approx(150e-9)],
        ["hls.lower", pytest.approx(80e-9)],
        ["bench.run", pytest.approx(50e-9)],
        [devtrace.OUTSIDE, pytest.approx(20e-9)]]
    assert sum(v for _, v in s.idle_self) == pytest.approx(
        s.window_s - s.busy_s)


def test_load_keeps_the_program_spans(tmp_path):
    """A trace taken here on the CPU: the host spans of the benchmark and
    of the program are kept, others are not."""
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.stretch"):
            with jax.profiler.TraceAnnotation("hls.dep_ilp"):
                jax.block_until_ready(jnp.ones(8) + 1)
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    _, spans = devtrace.load(devtrace.find_xplane(str(tmp_path)))
    assert {s.name for s in spans} == {"bench.stretch", "hls.dep_ilp"}


def test_reduce_counts_a_loop_body_once():
    """A ``while`` event spans the ops of its body: kernel and glue count
    the body's ops alone, busy time the union of all."""
    spans = [Span("bench.stretch", 0, 1000)]
    ops = [Op(0, "while", 100, 700, False),
           Op(0, "dynamic-slice", 100, 50, False),
           Op(0, "kernel", 150, 200, True),
           Op(0, "dynamic-slice", 400, 50, False),
           Op(0, "kernel", 450, 200, True),
           Op(0, "copy", 900, 50, False)]
    s = devtrace.reduce(ops, spans, n_devices=1)
    assert s.kernel_s == pytest.approx(400e-9)
    assert s.glue_s == pytest.approx(150e-9)
    assert s.busy_s == pytest.approx(750e-9)
    assert "while" not in [n for n, _ in s.device_ops]
    assert [o.name for o in devtrace.leaves(ops)].count("while") == 0


@pytest.mark.parametrize("name,kernel", [
    ('%_unknown_.1 = f32[1080,1920]{1,0:T(8,128)} custom-call(f32[1082,1922]'
     '{1,0:T(8,128)S(1)} %copy), custom_call_target="tpu_custom_call"', True),
    ('%custom-call = f32[64,1080,1920]{2,1,0:T(8,128)} custom-call(), '
     'custom_call_target="AllocateBuffer"', False),
    ('%copy = f32[1082,1922]{1,0:T(8,128)S(1)} copy(f32[1082,1922]'
     '{0,1:T(8,128)} %arrays__img__.1)', False),
])
def test_kernel_is_a_mosaic_custom_call(name, kernel):
    assert devtrace.is_kernel(name) is kernel


def test_reduce_averages_over_chips():
    spans = [Span("bench.stretch", 0, 100)]
    ops = [Op(0, "k", 0, 50, True), Op(1, "k", 0, 30, True)]
    s = devtrace.reduce(ops, spans, n_devices=2)
    assert s.kernel_s == pytest.approx(40e-9)
    assert s.busy_s == pytest.approx(40e-9)


def test_reduce_needs_one_stretch():
    with pytest.raises(ValueError):
        devtrace.reduce([], [], n_devices=1)


DATA = Path(__file__).resolve().parent / "data"


def _readings(path, calls, cfg_name):
    cfg, mod = bspec.load_config(cfg_name)
    ops, nbytes = mod.counts(cfg)
    return run.Readings(calls=calls, trace=devtrace.summarize(str(path), 1),
                        spans={}, ops=ops, nbytes=nbytes,
                        peak=peaks.peak_of("TPU v5 lite"))


def test_recorded_stream_trace():
    """200 calls of blur_hd.stream traced on a TPU v5e (chip run)."""
    ops, spans = devtrace.load(str(DATA / "blur_hd.stream.xplane.pb"))
    assert len(ops) == 400 and sum(o.kernel for o in ops) == 200
    r = _readings(DATA / "blur_hd.stream.xplane.pb", 200, "blur_hd")
    s = r.trace
    assert s.kernel_s == pytest.approx(0.006768127, rel=1e-9)
    assert s.glue_s == pytest.approx(0.002610809, rel=1e-9)
    assert s.busy_s == pytest.approx(0.009378936, rel=1e-9)
    assert s.window_s == pytest.approx(0.048312499, rel=1e-9)
    assert [n for n, _ in s.device_ops] == ["custom-call %_unknown_.1",
                                            "copy %copy"]
    assert s.idle_gaps == [["bench.wait", 0.001654679],
                           ["bench.dispatch", 0.000292971],
                           ["bench.dispatch", 0.000275564],
                           ["bench.dispatch", 0.00027450900000000004],
                           ["bench.dispatch", 0.000271584],
                           ["bench.dispatch", 0.00026734],
                           ["bench.dispatch", 0.000266648],
                           ["bench.dispatch", 0.00026531300000000004],
                           ["bench.dispatch", 0.000257076],
                           ["bench.dispatch", 0.000256603]]
    assert s.idle_self == [["bench.dispatch", pytest.approx(0.031797192)],
                           ["bench.wait", pytest.approx(0.004675581)],
                           [devtrace.OUTSIDE, pytest.approx(0.00246079)]]
    assert sum(v for _, v in s.idle_self) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    read = {m: bspec.load_reader(m)(r) for m in
            ("kernel_us", "glue_us", "generated_run_roofline", "idle_share")}
    assert read["kernel_us"] == pytest.approx(33.840635, rel=1e-9)
    assert read["glue_us"] == pytest.approx(13.054045, rel=1e-9)
    least = 16_612_816 / 819e9
    assert read["generated_run_roofline"] == pytest.approx(
        100 * least / (0.009378936 / 200), rel=1e-9)
    assert 0 < read["generated_run_roofline"] <= 100
    assert read["idle_share"] == pytest.approx(
        100 * (1 - 0.009378936 / 0.048312499), rel=1e-9)


def test_recorded_recompile_trace():
    """One compile step of blur_hd.recompile traced on a TPU v5e."""
    s = devtrace.summarize(str(DATA / "blur_hd.recompile.xplane.pb"), 1)
    assert s.kernel_s == pytest.approx(3.4801e-05, rel=1e-9)
    assert s.busy_s == pytest.approx(4.8259e-05, rel=1e-9)
    assert s.window_s == pytest.approx(0.682559773, rel=1e-9)
    assert s.idle_gaps == [["bench.dse", 0.6814702020000001],
                           ["bench.run", 0.001041311], ["bench.run", 1e-09]]
    assert s.idle_self == [["bench.dse", pytest.approx(0.559657538)],
                           ["bench.xla_compile", pytest.approx(0.119132193)],
                           ["bench.run", pytest.approx(0.002163061)],
                           ["bench.lower", pytest.approx(0.001015641)],
                           [devtrace.OUTSIDE, pytest.approx(0.000543081)]]


@pytest.mark.parametrize("name,kind", [
    ("%copy = f32[1082,1922]{1,0:T(8,128)S(1)} copy(f32[1082,1922]"
     "{0,1:T(8,128)} %arrays__img__.1)", "copy"),
    ("%_unknown_.1 = (f32[180,190]{1,0:T(8,128)}, f32[180,220]"
     "{1,0:T(8,128)}) custom-call(f32[180,210]{1,0:T(8,128)S(1)} %c), "
     "custom_call_target=\"tpu_custom_call\"", "custom-call"),
    ("%copy-start = (f32[190,220]{1,0:T(8,128)S(1)}, u32[]) "
     "copy-start(f32[190,220]{1,0:T(8,128)} %a)", "copy-start")])
def test_op_kind(name, kind):
    assert devtrace.op_kind(name) == kind
