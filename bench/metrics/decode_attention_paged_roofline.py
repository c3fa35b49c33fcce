"""Share of its roofline that the paged decode-attention kernel
(``_kernel_paged``) reaches over the traced decode ticks, in %: the least
time the chip could take for the live rows' work, max(operations / peak
FLOP/s, bytes / peak bandwidth), over the kernel's device time. One call per
layer per decode tick, each layer's work with its own window of keys; the
bandwidth bound applies at these shapes.

The trace names a Pallas kernel only by its HLO text, so the kernel is found
by its operands: three scalar-prefetch ints (positions, live, and the 2-D
block table, which only the paged kernel has), the grouped query, and the two
pools viewed as (blocks, block, K * Dh)."""
from bench import core, readers
from bench import trace as tr

KERNEL = (r"custom-call\(s32\[\d+\]\S* \S+, s32\[\d+\]\S* \S+, "
          r"s32\[\d+,\d+\]\S* \S+, bf16\[\d+,\d+,\d+,\d+\]\S* \S+, "
          r"bf16\[\d+,\d+,\d+\]\S* \S+, bf16\[\d+,\d+,\d+\]")


def read(run):
    hit = run.get("trace") and tr.op_time(run["trace"], KERNEL)
    if not hit:
        return None
    cost = core.module("roofline", "decode_attention_paged", run["root"]).cost
    layers = readers.windows(run)
    least = sum(readers.least_s(run, cost, tick["decode"], layers)
                for tick in readers.traced_ticks(run) if tick["decode"])
    return readers.share(least, hit[0])
