"""Share of the window the sender spent waiting for the server: inside a
call, waiting for its ack, or holding frames back until the query came
within the traffic's lead. The rest it spent waiting for its own
encoders: low means the generator, not the server, set the pace."""


def read(run: dict, spec: dict):
    lo, hi = run["start"]["t"], run["end"]["t"]
    inside = sum(max(0.0, min(c[3], hi) - max(c[2], lo))
                 for c in run["calls"])
    return 100.0 * (inside + run["producer"]["lead_wait_s"]) \
        / run["window_s"]
