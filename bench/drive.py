"""The traffic generator: makes a cell's inputs from the seed and drives the
compiler's entry points as a mix's parameters say.

Two kinds of mix, named by a traffic file's ``kind``:

* ``stream``: a batch pipeline.  One jitted call runs the compiled kernel
  over ``batch`` frames (``lax.map``, one kernel call per frame); calls go
  back to back on a rotating set of ``distinct_inputs`` batches, at most
  ``in_flight`` outstanding.
* ``recompile``: a developer's edit-compile-run loop: each step draws new
  constants for the program, compiles it through the whole user path and
  runs it once.

The user path is the program's own, imported and not copied:
``hls.compile`` -> ``CompileResult.knee`` -> ``codegen._point_block_rows``
+ ``codegen.lower_program`` at the deployment size -> ``jax.jit``.
"""
from __future__ import annotations

import random
import time
import traceback
from collections import defaultdict, deque
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hls
from repro.core.codegen import _point_block_rows, lower_program


class Spans:
    """Host spans around the calls into each layer, kept in memory.  Each
    span is also a profiler annotation (``bench.<name>``), so a traced
    run puts it on the device trace's clock."""

    def __init__(self):
        self.durations: dict[str, list[float]] = defaultdict(list)

    @contextmanager
    def __call__(self, name: str):
        with jax.profiler.TraceAnnotation("bench." + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)


def seed_key(seed: int):
    """A JAX key from any whole number, however many bits it has."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def varying(dists: dict) -> tuple[str, ...]:
    """The arrays that differ from frame to frame: those with a ``uniform``
    distribution.  Every other array is zero and shared."""
    return tuple(sorted(n for n, d in dists.items() if "uniform" in d))


def make_inputs(program, dists: dict, dtype: str, seed: int, sets: int,
                batch: int | None = None) -> list[dict]:
    """``sets`` input sets for every array of ``program``, made on the
    device in one jitted call.  A varying array differs per set; with
    ``batch`` it carries a leading axis of that many frames, each drawn
    apart.  Every other array is zero, unbatched and shared."""
    dt = jnp.dtype(dtype)
    lead = (batch,) if batch else ()
    rand = [(k, name, lead + a.shape, *dists[name]["uniform"])
            for k, (name, a) in enumerate(program.arrays.items())
            if name in varying(dists)]
    zero = [(name, a.shape) for name, a in program.arrays.items()
            if name not in varying(dists)]

    def gen(key):
        out = []
        for f in range(sets):
            kf = jax.random.fold_in(key, f)
            out.append({name: jax.random.uniform(jax.random.fold_in(kf, k),
                                                 shape, dt, lo, hi)
                        for k, name, shape, lo, hi in rand})
        return out, {name: jnp.zeros(shape, dt) for name, shape in zero}

    made, zeros = jax.jit(gen)(seed_key(seed))
    return [{**m, **zeros} for m in made]


def frame_of(arrays: dict, mapped: tuple, k: int) -> dict:
    """Frame ``k`` of a batch: the mapped arrays' ``k``-th slice, the
    shared arrays as they are."""
    return {n: (v[k] if n in mapped else v) for n, v in arrays.items()}


def over_frames(fn, mapped: tuple):
    """``fn`` over a batch of frames in one program: the arrays named in
    ``mapped`` carry a leading axis of frames, and ``lax.map`` calls the
    kernel once per frame with the shared arrays beside them."""
    def run(arrays):
        shared = {n: v for n, v in arrays.items() if n not in mapped}
        return jax.lax.map(lambda x: fn({**x, **shared}),
                           {n: arrays[n] for n in mapped})
    return run


def compile_once(cfg: dict, mod, consts: dict, example: dict, spans: Spans,
                 interpret: bool = False, mapped: tuple = ()):
    """One compile through the user path: the DSE at the configuration's
    DSE size, its knee lowered at the deployment size, the kernel jitted
    (over a batch of frames where ``mapped`` names the batched arrays) and
    compiled for ``example``'s shapes.  Returns (kernel, knee, jitted
    function); the function is compiled and calling it compiles nothing."""
    dse = cfg["dse"]
    search = hls.SearchConfig(**{k: tuple(v) if isinstance(v, list) else v
                                 for k, v in dse["search"].items()})
    with spans("dse"):
        r = hls.compile(mod.program(cfg, consts, dse=True),
                        objectives=tuple(hls.minimize(o)
                                         for o in dse["objectives"]),
                        search=search)
        knee = r.knee(*dse["knee"])
    with spans("lower"):
        kernel = lower_program(mod.program(cfg, consts),
                               block_rows=_point_block_rows(knee),
                               dtype=cfg["dtype"])
    run = partial(kernel.fn, interpret=interpret)
    f = jax.jit(over_frames(run, mapped) if mapped else run)
    with spans("xla_compile"):
        f.lower(example).compile()
    return kernel, knee, f


class Reservoir:
    """A uniform sample of ``size`` answers out of a stream of unknown
    length, drawn from the seed; the last answer is always kept too."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = random.Random(seed)
        self.kept: list = []
        self.seen = 0
        self.last = None

    def offer(self, item) -> None:
        if len(self.kept) < self.size:
            self.kept.append(item)
        else:
            j = self.rng.randrange(self.seen + 1)
            if j < self.size:
                self.kept[j] = item
        self.seen += 1
        self.last = item

    def sample(self) -> list:
        return self.kept + ([self.last] if self.last is not None
                            and all(self.last is not k for k in self.kept)
                            else [])


def stream(f, frames: list, seconds: float, in_flight: int,
           reservoir: Reservoir) -> tuple[int, float]:
    """Call ``f`` back to back on the rotating input sets for ``seconds``,
    with at most ``in_flight`` calls outstanding; the window ends when the
    last call is ready.  Returns (calls completed, window seconds).
    Sampled answers go to ``reservoir`` as (set index, outputs)."""
    pending = deque()
    n = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        fi = n % len(frames)
        out = f(frames[fi])
        reservoir.offer((fi, out))
        pending.append(out)
        n += 1
        if len(pending) >= in_flight:
            jax.block_until_ready(pending.popleft())
    jax.block_until_ready(list(pending))
    return n, time.perf_counter() - t0


def stream_stretch(f, frames: list, calls: int, in_flight: int,
                   spans: Spans) -> None:
    """``calls`` calls as the window makes them, each dispatch and wait
    annotated, for a traced stretch."""
    pending = deque()
    with spans("stretch"):
        for n in range(calls):
            with spans("dispatch"):
                pending.append(f(frames[n % len(frames)]))
            if len(pending) >= in_flight:
                with spans("wait"):
                    jax.block_until_ready(pending.popleft())
        with spans("wait"):
            jax.block_until_ready(list(pending))


def edit_compile_run(cfg: dict, mod, consts: dict, frame: dict, spans: Spans,
                     interpret: bool = False):
    """One step of the recompile mix: the program with ``consts`` compiled
    through the user path, and its kernel called once on ``frame`` (the
    call is not waited for)."""
    _, _, f = compile_once(cfg, mod, consts, frame, spans, interpret)
    with spans("run"):
        return f(frame)


def recompile(cfg: dict, mod, frame: dict, seconds: float,
              rng: np.random.Generator, reservoir: Reservoir, spans: Spans,
              interpret: bool = False) -> tuple[int, int, float]:
    """Edit-compile-run steps for ``seconds``: each draws constants from
    ``rng``, compiles through the user path and runs the kernel once on
    ``frame``.  A step starts only while time remains, and the last one
    started is finished.  Returns (compiles, compiles that raised, window
    seconds).  Answers go to ``reservoir`` as (constants, outputs)."""
    n = failed = 0
    outs = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        consts = mod.consts(cfg, rng)
        n += 1
        try:
            out = edit_compile_run(cfg, mod, consts, frame, spans, interpret)
        except Exception:  # a compile that fails is a failed attempt
            traceback.print_exc()
            failed += 1
            continue
        reservoir.offer((consts, out))
        outs = [out]
    jax.block_until_ready(outs)
    return n, failed, time.perf_counter() - t0
