"""The TOS macro's write errors on the chip: the least time their injection
needs, counted from the algorithm (``bench/reference_ntv.py``) and not
from any implementation of it, and the device self time of the program's
``ber_inject`` scope.

Per lane-chunk folded at a BER above 0 (``inject_cost``), for a frame of
``px`` pixels:

- bytes: the surface read and written back, 1 B a pixel each way (the
  random bits are made where they are used and need no memory traffic);
- operations per pixel: five 32-bit random words from threefry2x32, the
  counter-based generator ``jax.random`` defines the bits with (20
  rounds of add, rotate and xor on a pair of words and 5 key injections
  of 2 adds: 70 operations a pair, 35 a word, a rotate counted as one);
  per bit the word's conversion to a uniform float (shift, or, subtract)
  and its compare with the BER, 4, and its place in the 5-bit mask
  (shift, or), 2; per pixel the encode (compare, subtract, select), the
  xor, the select that spares code 0 (compare, select) and the decode
  (compare, add, select), 9.

The larger of bytes over peak bandwidth and operations over peak rate
(``bench/peaks.json``) is the least time.  The peak rate is the chip's
bf16 matrix rate: the vector unit that does this integer work is slower,
so the operations bound is low and the bytes bound decides.
"""
from __future__ import annotations

import pathlib

from bench import program_trace

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCOPE = "ber_inject"
ROOFLINE_METRIC = "ber_inject_roofline_share.sat"
WORD_OPS = 35           # threefry2x32: 70 operations a pair of words
BIT_OPS = 4 + 2         # uniform float, compare; its place in the mask
PIXEL_OPS = 3 + 1 + 2 + 3
BITS = 5


def inject_cost(px: int) -> tuple[int, int]:
    """(operations, bytes) of one lane-chunk's write errors."""
    ops = px * (BITS * (WORD_OPS + BIT_OPS) + PIXEL_OPS)
    return ops, 2 * px


def least_time_s(lane_chunks: int, px: int, peak: dict) -> float:
    """The larger of bytes over peak bandwidth and operations over peak
    rate for ``lane_chunks`` lane-chunks at a BER above 0."""
    ops, nbytes = inject_cost(px)
    return max(lane_chunks * nbytes / peak["hbm_bytes_per_s"],
               lane_chunks * ops / peak["flops_per_s"])


def frame_pixels(root: pathlib.Path = ROOT) -> int:
    """Pixels of a frame of the cells ``ROOFLINE_METRIC`` lists in
    ``BENCHMARK.json``: a metric reader is not told its configuration, so
    the cells the metric lists must share one geometry."""
    from bench import harness

    bench = harness.Bench(root)
    [metric] = [m for m in bench.spec["per_layer"]
                if m["name"] == ROOFLINE_METRIC]
    sizes = set()
    for cell in metric["workloads"]:
        config = bench.config(bench.workload(cell)["config"])
        sizes.add(int(config["height"]) * int(config["width"]))
    if len(sizes) != 1:
        raise ValueError(f"{ROOFLINE_METRIC}'s cells differ in frame size: "
                         f"{sorted(sizes)}")
    return sizes.pop()


def scope_self_s(ctx: dict, trace_dir=None):
    """Device self seconds of the ``ber_inject`` scope in the traced
    window, kept in ``ctx["ber_inject_s"]`` for the run's other readers;
    None for an untraced run, where no trace is found, or for a program
    without the scope.  ``program_trace.reduce`` names the scopes of its
    ``SCOPES``, which the scope postdates: it is added there for this one
    reduction (operations under no named scope would otherwise take the
    scope of their users)."""
    if "ber_inject_s" not in ctx:
        ctx["ber_inject_s"] = None
        if ctx.get("trace") is None:
            return None
        try:
            planes = program_trace.load(str(trace_dir
                                            or program_trace.TRACE_DIR))
        except FileNotFoundError:
            return None
        scopes = program_trace.SCOPES
        program_trace.SCOPES = scopes + (SCOPE,)
        try:
            red = program_trace.reduce(planes, hlo=program_trace.hlo_scopes(
                program_trace.executor_hlo()))
        finally:
            program_trace.SCOPES = scopes
        ctx["ber_inject_s"] = red["scope_self_s"].get(SCOPE)
    return ctx["ber_inject_s"]
