"""Device time of the serving programs' group-by per query completed in
the traced window, in ms: ops in the named scopes ``group_key`` and
``aggregate`` (group-key gathers, measure, total, segment sum) and the
custom fusions the TPU compiler leaves without an ``op_name``, which are
the segment sum's scatter-add (``benchkit/scopes.py``).  One metric: XLA
may fuse the key gathers into the scatter-add."""
from benchkit import scopes


def read(ctx):
    return scopes.scope_ms_per_query(
        ctx, ("group_key", "aggregate", scopes.UNNAMED_CUSTOM))
