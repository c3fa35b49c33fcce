"""Useful operations of the traced chunk rounds over their program's device
time times the chip's peak, in %. Useful: each real prompt token through
every block, causal attention over the real context each layer reads, and
the output head at a prompt's last position only; padding rows and padded
positions are not."""
from bench import readers
from bench import trace as tr


def read(run):
    t = run.get("trace") and tr.program_time(run["trace"], "_chunk_fn")
    if not t:
        return None
    cnt, rank = readers.counts(run), run["traffic"]["users"]["rank"]
    flops = sum(cnt.forward_flops(run["config"], p, c, rank, int(last))
                for tick in readers.traced_ticks(run)
                for p, c, last in tick["chunk"])
    return readers.share(flops / run["peak"]["bf16_flops"], t[0])
