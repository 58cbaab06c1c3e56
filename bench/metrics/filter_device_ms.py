"""Device time of the serving programs' ops in the named scope ``filter``
(join mask, dimension predicates gathered through the probe's rows, fact
filter) per query completed in the traced window, in ms."""
from benchkit import scopes


def read(ctx):
    return scopes.scope_ms_per_query(ctx, ("filter",))
