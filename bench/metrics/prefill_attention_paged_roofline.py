"""Share of its roofline that the chunked-prefill attention kernel
(``prefill_attention_paged``) reaches over the traced chunk rounds, in %:
the least time the chip could take for the prefilling rows' work, max
(operations / peak FLOP/s, bytes / peak bandwidth), over the kernel's device
time. One call per layer per chunk round, each layer's work with its own
window of keys. The kernel is found by the name it carries in the trace; a
program without it (or one whose chunks fell back to the oracle) gives
nothing to read."""
from bench import core, phases, readers
from bench import trace as tr


def read(run):
    hit = run.get("trace") and tr.op_time(
        run["trace"], phases.kernel("prefill_attention_paged"))
    if not hit:
        return None
    cost = core.module("roofline", "prefill_attention_paged", run["root"]).cost
    layers = readers.windows(run)
    least = sum(readers.least_s(run, cost,
                                [(p, c) for p, c, _ in tick["chunk"]], layers)
                for tick in readers.traced_ticks(run) if tick["chunk"])
    return readers.share(least, hit[0])
