"""Share of the traced window in which no operation ran on the device,
averaged over the chips used.  Layer: device.  Moves tpot_p95_ms."""
from bench.trace import busy_intervals, union_length


def read(r):
    lo, hi = r.window
    shares = [1.0 - union_length([iv for iv in busy_intervals(d)
                                  if iv[1] > lo and iv[0] < hi]) / (hi - lo)
              for d in r.devices()]
    return 100.0 * sum(shares) / len(shares) if shares else None
