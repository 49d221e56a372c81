"""collective_share.dist: device time in collectives (the reduce-scatter of
the partial C) with no other operation running beside them, as a share, in %,
of the device's busy time, averaged over the chips."""

from chipbench.trace import is_collective


def read(ctx):
    t = ctx["trace"]
    if not t.kernel_seconds(is_collective):
        return None
    return 100.0 * t.exposed_seconds(is_collective) / t.busy_s()
