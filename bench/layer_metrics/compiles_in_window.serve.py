"""XLA backend compiles between the window's start and its end: each
is a shape the set-up did not warm, and a stall of the decode tick for
every running request.  Set-up warms every shape of the schedule, so
this reads 0 unless a change adds a shape.  Layer: serving scheduler.
Moves tpot_p95_ms."""


def read(r):
    return float(len(r.record["compiles_in_window"]))
