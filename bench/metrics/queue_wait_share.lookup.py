"""Share (%) of the open-loop lookups' latency spent queued in the service:
the ``queue_wait_s`` of the window's finished lookups (from the submit of a
dispatch batch's first request to the start of its executor call) over the
sum of their latencies (due time to last chunk).  None where the program
keeps no such counter."""


def read(ctx):
    if ctx["loop"] != "open":
        return None
    done = [q for q in ctx["queries"] if q["end"] is not None
            and q.get("ok", True) and q["stats"]]
    if not done or "queue_wait_s" not in done[0]["stats"]:
        return None
    latency = sum(q["end"] - q["due"] for q in done)
    wait = sum(q["stats"]["queue_wait_s"] for q in done)
    return 100.0 * wait / latency if latency > 0 else None
