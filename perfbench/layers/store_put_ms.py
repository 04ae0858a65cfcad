"""Host IO (``io/store.py``): the enqueue of the host-to-device copy and
the wait for it, in host milliseconds per query, median over the window.
The program's ``store.put`` span records the enqueue only (it adds no
sync); in a traced run the harness's enclosing ``store_read`` /
``sql_front`` span ends when the data is on the device, so what lies
between the end of the program's ``store.read`` and the end of that
span is the wait.  Source: program span (and the harness's span for the
wait)."""

from perfbench import program_spans as ps

OUTER = ("store_read", "sql_front")


def read(run):
    ends = {q: t1 for q, n, _t0, t1 in run["spans"].rows if n in OUTER}

    def one(qrows):
        put = [r for r in qrows if r.name == "store.put"]
        if not put:
            return None
        s = sum(r.seconds for r in put)
        reads = [r.t1 for r in qrows if r.name == "store.read"]
        if put[0].query in ends and reads:
            s += max(0.0, ends[put[0].query] - max(reads))
        return s
    return ps.ms(ps.median_per_query(run, one))
