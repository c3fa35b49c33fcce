"""Host time per traced step of Mode A's offloaded fit: the union of the op
intervals of the fit program (``core/offload.py``'s jitted ``_fit``, module
``jit__fit``) on the host's XLA device, over the steps traced. (The
program's ``offload.fit`` annotation closes when the fit is dispatched, not
when it is done, so it is not read.)"""


def read(run):
    t = run.get("trace")
    steps = t and t["spans"].get("bench.step")
    fit = t and [v["busy_s"] for m, v in t["host_programs"].items()
                 if m.startswith("jit__fit")]
    if not fit or not steps or not steps["count"]:
        return None
    return 1e3 * sum(fit) / steps["count"]
