"""Own device time of a decode dispatch under NO part scope, as a share of the
program's device time, in SDAR's cell: ``decode_unscoped_pct``'s reader and
specification, whole (that entry's ``workloads`` is not a later PR's to
edit). What a block program leaves outside the eight parts: the scans'
carries between passes and blocks, the position grids."""

from bench.layer_metrics.decode_unscoped_pct import read  # noqa: F401
