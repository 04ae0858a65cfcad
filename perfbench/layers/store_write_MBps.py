"""Host IO (``io/store.py``, ``native/``): the ``bytes`` the program's
``store.file_write`` span says it wrote (``native.write_files``, from
the segments' shapes) over that span's seconds, per query, median over
the window.  Source: program span."""

from perfbench import program_spans as ps


def read(run):
    def one(qrows):
        hit = [r for r in qrows if r.name == "store.file_write"]
        secs = sum(r.seconds for r in hit)
        if not hit or secs <= 0:
            return None
        return sum(r.attrs.get("bytes", 0) for r in hit) / secs / 1e6
    return ps.median_per_query(run, one)
