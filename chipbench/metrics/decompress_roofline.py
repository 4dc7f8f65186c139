"""decompress_roofline (layer: wire codec, ``runtime/wire.py`` ->
``kernels/bottleneck_decompress.py``): the ae8 decode kernel's share of
its roofline, in %.  Its device time is that of the Pallas kernel's
operations inside the program of ``wire._decode_jit`` in the traced
span; the least time of one call (one frame) is the larger of its
operations over the bf16 peak and its bytes over HBM bandwidth
(``flops.decode_cost``).

Matches: in the program ``jit__decode_jit``, the operations whose HLO
instruction is named ``bottleneck_decompress[.n]``: the custom call that
``pallas_call`` makes, named after the jitted wrapper
``kernels.bottleneck_decompress.bottleneck_decompress``."""
from chipbench import flops

MODULE = "jit__decode_jit"
KERNEL = "bottleneck_decompress"


def read(rec):
    if rec.trace is None:
        return None
    calls, secs = rec.trace.op_seconds(
        lambda mod, op: mod == MODULE and op.split(".")[0] == KERNEL)
    if not calls or secs <= 0:
        return None
    least = flops.least_seconds(*flops.decode_cost(rec.cfg), rec.peaks)
    return 100.0 * calls * least / secs
