"""Share of the chip's bf16 peak one prefill dispatch reaches, in %.

The least arithmetic of a dispatch — ``prefill_flops`` of the ``shapes.py``
in the configuration's architecture package, over the prompt tokens a
dispatch carried (the program's ``serve_prefill_tokens`` over its
``serve_prefill_dispatches``, in prompts of ``serve_prefill_tokens`` over
``serve_admitted`` tokens) — over the chip's published bf16 FLOP/s and the
measured device time of one prefill program (``trace_program``: the
variant that ran most often).  None where the package has no such
function, the program no such counters, or the trace no prefill program.
"""

from harness import arch, peaks
from readers import counter_ratio, trace_program


def read(ctx, program: str, exclude: str = ""):
    program_ms = trace_program.read(ctx, program, exclude)
    flops = getattr(
        arch.load_shapes(ctx["conf"]).shapes, "prefill_flops", None)
    tokens = ["serve_prefill_tokens"]
    a_dispatch = counter_ratio.read(ctx, tokens, ["serve_prefill_dispatches"])
    a_prompt = counter_ratio.read(ctx, tokens, ["serve_admitted"])
    if program_ms is None or flops is None or not a_dispatch or not a_prompt:
        return None
    peak = peaks.peaks_of(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * flops(ctx["conf"], a_dispatch, a_prompt) / (
        peak * program_ms * 1e-3)
