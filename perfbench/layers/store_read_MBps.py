"""Host IO (``io/store.py``, ``native/``): stored bytes over the host
seconds of ``ctx.from_store`` (it reads, verifies and puts on the
device), ended by ``block_until_ready``; median over the window's
queries.  In a SQL cell ``sql.query`` makes that call inside its
lowering, so there it is the seconds of ``sql.query`` less those of
parse and bind.  Source: host clock (a traced run: the wait for the
device is part of the span)."""

import statistics


def read(run):
    sp = run["spans"]
    secs = sp.seconds("store_read")
    if not secs:
        front = sp.seconds("sql_front")
        bind = sp.seconds("sql_bind")
        if not front:
            return None
        b = statistics.median(bind) if bind else 0.0
        secs = [f - b for f in front]
    t = statistics.median(secs)
    return run["state"]["stored_bytes"] / t / 1e6 if t > 0 else None
