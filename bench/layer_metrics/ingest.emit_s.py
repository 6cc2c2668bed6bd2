"""Store build and write seconds per job: the mean of the ``emit_kgz``
(``kg/store.py`` build and ``kg/persist.py`` write) or ``emit_nt``
(the N-Triples writer) spans of the window's jobs."""


def read(ctx):
    spans = ctx.span_seconds("emit_kgz", "emit_nt")
    return sum(spans) / len(spans) if spans else None
