"""score_mask_ms (layer: kernels, kernels/sparse_matmul.py): device time
of the WiSparse score/mask kernel (``wisparse_score_mask``, its Pallas
call's name and named scope) inside ``repro/decode`` annotations, per
traced decode step, in ms (profiler trace).  None where no op carries
the name, as in a program whose kernels are anonymous."""
from bench import trace

# The TPU trace names an op by its HLO text, which starts with the
# instruction's own name (``%wisparse_score_mask.28 = (bf16[32,8192]...)
# custom-call(...)``) and goes on to its operands: anchored, so that an op
# reading the kernel's result is not counted.  Elsewhere the named scope
# may reach the framework name (``.../wisparse_score_mask/pallas_call``).
KERNEL = r"^%?wisparse_score_mask(\.\d+)? = |/wisparse_score_mask/"


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    ns, n = trace.kernel_ns(tr, KERNEL, "repro/decode", run.trace_lo,
                            run.trace_hi)
    steps = sum(1 for s in tr.notes if s.name == "repro/decode"
                and run.trace_lo <= s.start <= run.trace_hi)
    if n == 0 or steps == 0:
        return None
    return ns / steps * 1e-6
