"""repro.ingest — the Dynamic D of D4M: LSM-style streaming mutation.

Resident tables (the serve layer) could serve but never absorb data;
this package adds the Accumulo-flavored write path over all three array
layers:

* :class:`~repro.ingest.table.IngestTable` — per-table **delta**
  absorbing raw triple batches (for ``AssocTensor`` a device-resident
  canonical delta folded batch by batch; for ``DistAssoc`` host batches
  key-partitioned straight to the owning row shard, zero collectives),
  **merge-on-read** (a device selection reads base and delta apart and
  ⊕-combines the two results; any other query reads the memoized
  whole-table merge), and **compaction** that folds delta into a new
  base off the readers' lock, bumps the table version, and invalidates
  the planner/compile cache entries keyed on the retired arrays.
* :class:`~repro.ingest.table.Compactor` — background thread compacting
  on a depth threshold or when the writer goes idle.
* :mod:`~repro.ingest.merge` — the compiled merge programs, contract-
  checked by ``tools/d4mcheck``: ``ingest.append`` and the merge-on-read
  programs are zero-collective and never densify.
"""
from .table import Compactor, IngestTable

__all__ = ["Compactor", "IngestTable"]
