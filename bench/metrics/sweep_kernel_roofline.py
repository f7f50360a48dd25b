"""The sweep kernel's share of its memory roofline in the traced window.

Work, counted from the algorithm: the spin updates the window's jobs
needed (busy slot-sweeps x spins per replica).  Least bytes: one f32
read and one f32 write per update, 8 B.  Least time: bytes over the
chip's published HBM bandwidth.  Share: least time over the summed
device time of the kernel's events.  The TPU v5e has no published f32
vector-unit peak, so no compute bound is taken, and the share is
against memory alone."""

BYTES_PER_UPDATE = 8


def read(ctx):
    t = ctx.get("trace")
    if not t or t["kernel_s"] <= 0:
        return None
    updates = t["counters"].get("busy_slot_sweeps", 0) * ctx["spins_per_replica"]
    if updates <= 0:
        return None
    least_s = updates * BYTES_PER_UPDATE / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["kernel_s"]
