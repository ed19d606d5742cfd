"""Host time per decode sync that leaves the chip idle, in ms: the mean,
over the program's ``engine.decode_sync`` spans that lie inside the traced
window, of each span's duration less its ``engine.host_sync`` children
(where the host waits for the decode loop on the device).  What remains is
the host's own work per sync: block allocation, copy-on-write and table
upload (``engine.kv_prep``), dispatch, and streaming the tokens out.  None
where the trace holds no such span."""

SYNC = "engine.decode_sync"
WAIT = "engine.host_sync"


def read(ctx):
    if ctx.trace is None:
        return None
    host = ctx.trace.host
    syncs = [e for e in host if e.name == SYNC
             and ctx.lo <= e.start_ns and e.end_ns <= ctx.hi]
    if not syncs:
        return None
    waits = [e for e in host if e.name == WAIT]
    own = []
    for s in syncs:
        waited = sum(w.dur_ns for w in waits
                     if s.start_ns <= w.start_ns and w.end_ns <= s.end_ns)
        own.append(s.dur_ns - waited)
    return sum(own) / len(own) / 1e6
