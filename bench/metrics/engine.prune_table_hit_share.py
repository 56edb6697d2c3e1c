"""engine.prune_table_hit_share: share (%) of the window's design-space
table lookups of pruning that found the table cached
(``cim_engine_prune_table_total``, one lookup per pruned job).  ``None``
for a program without the family (one that enumerates the space for
every job) or a window without a lookup."""
from _common import delta

FAMILY = "cim_engine_prune_table_total"


def read(run):
    hit, miss = (f'{FAMILY}{{outcome="{o}"}}' for o in ("hit", "miss"))
    if hit not in run.reg1 or miss not in run.reg1:
        return None
    hits = delta(run, hit)
    lookups = hits + delta(run, miss)
    if lookups == 0:
        return None
    return 100.0 * hits / lookups
