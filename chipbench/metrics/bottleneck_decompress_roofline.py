"""bottleneck_decompress_roofline (layer: wire codec,
``kernels/bottleneck_decompress.py`` as the prologue of the tail
server's program, ``Partition.served_tail``): the ae8 decode kernel's
share of its roofline, in %, where one call decodes the whole slot pool.
Its device time is that of the Pallas kernel's operations inside the
served program in the traced span; the least time of one call is the
larger of its operations over the bf16 peak and its bytes over HBM
bandwidth (``flops.decode_cost`` over ``n_slots * client_batch``
images).

Matches: in the program ``jit__lambda``, the operations whose HLO
instruction is named ``bottleneck_decompress[.n]``.  A server that
decodes each frame in its own ``jit__decode_jit`` program (read by
``decompress_roofline``) runs no such operation there, and this reads
nothing."""
from chipbench import flops

MODULE = "jit__lambda"
KERNEL = "bottleneck_decompress"


def read(rec):
    if rec.trace is None:
        return None
    calls, secs = rec.trace.op_seconds(
        lambda mod, op: mod == MODULE and op.split(".")[0] == KERNEL)
    if not calls or secs <= 0:
        return None
    images = rec.cfg["n_slots"] * rec.cfg["client_batch"]
    least = flops.least_seconds(*flops.decode_cost(rec.cfg, images),
                                rec.peaks)
    return 100.0 * calls * least / secs
