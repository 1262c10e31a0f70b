"""Which programs of a trace are Q3's join and group-by, and which its
top-N: what `join_groupby_roofline` and `topn_busy_share` look for, by
dispatch-ledger label (`lib/groupby_programs.modules_of` finds the XLA
modules through the program's own `obs.dispatch.module_labels()`)."""

from .join_programs import JOIN_LABELS

#: the fused join stage's programs (sizing; the probe step, which builds the
#: table, probes, projects and groups in one program, in its masked-bucket
#: and its many-group form) and the per-operator join of the build side
JOIN_GROUPBY_LABELS = JOIN_LABELS

#: the result sort under the limit, and the program that measures a string
#: key's width for it
TOPN_LABELS = frozenset({"SortExec.sort", "sort.key_width"})
