"""Share of its roofline the campaign's walk reached on the slowest
chip: for each chip, the least time its walked moves in the traced span
need at the chip's HBM peak (per move the first-move byte, the next
node and the arc's weight), over the device time of its walk programs
(``mesh_walk``) in the span; the share of the chip whose walk took
longest. The walk is bound by gather latency, so the share is small;
it is read as a ratio between commits."""

from kernels import walk_bytes
from trace_reduce import DEVICE_PREFIX, program_s

WALK = "mesh_walk"


def read(run):
    tr = run.get("trace")
    moves = run.get("walk_moves_by_device") or {}
    if (run.get("kind") != "campaign" or tr is None or not moves
            or not run.get("peaks")):
        return None
    chips = []
    for dev in tr.devices:
        seconds = program_s(dev, WALK)[0]
        did = int(dev.name[len(DEVICE_PREFIX):])
        if seconds > 0 and moves.get(did):
            least = walk_bytes(moves[did]) / run["peaks"]["hbm_bytes_per_s"]
            chips.append((seconds, 100.0 * least / seconds))
    return max(chips)[1] if chips else None
