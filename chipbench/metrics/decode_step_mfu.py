"""The decode step's share of the chip's peak (%): for each step, the
larger of its operations over peak FLOP/s and its bytes (every weight,
the cache slots read) over peak HBM bandwidth, summed over the window's
steps and divided by their device time."""
from lib.trace import module_time


def read(data):
    if not data.summary:
        return None
    secs, n = module_time(data.summary, "decode_step")
    steps = data.extras.get("decode_steps_by_slots", {})
    if not n or n != sum(steps.values()):
        return None
    c, pk = data.counts, data.peaks
    ideal = sum(k * max(c.decode_flops(slots) / pk["bf16_flops_per_s"],
                        c.decode_bytes(slots) / pk["hbm_bytes_per_s"])
                for slots, k in steps.items())
    return 100.0 * ideal / secs
