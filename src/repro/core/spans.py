"""Names of the serving path's host spans.

Each is a ``jax.profiler.TraceAnnotation``, always in the code and recorded
only while a profiler session runs; with none, one costs well under a
microsecond.  They sit on the device trace's clock, beside the device's
ops, so an idle gap on the device can be put down to a named piece of host
code.  The arguments named below go into the trace as event stats.

- ``match.batch`` — one ``WatchlistCartridge.process_batch`` call.  The
  four phases below tile it, apart from the grouping loop itself:
- ``match.scope`` — which rows each query screens against: the
  cartridge's tenant grouping and each group's stacking of queries, and
  ``SecureGallery.match``'s argument checks and scope row count.
- ``match.protect`` — the queries' keyed rotation.
- ``match.scan`` — the search, one span per shard (prepared view, tenant
  row subset, kernel call, indices to the host), one for the ANN coarse
  scan and one for a top-k merge across shards; arg ``index_bytes`` on a
  shard's span when a host row index goes with a tenant subset.
- ``match.results`` — answers back to the caller: match stats, the label
  lookup, scores, the cartridge's result messages; arg ``labels`` (the
  labels the call looked up, queries times k) on the gallery's span.
- ``cartridge.call`` / ``cartridge.sync`` — ``Cartridge.process``: the
  host's dispatch of one stage call, then its wait for the result.
"""

MATCH_BATCH = "match.batch"
MATCH_SCOPE = "match.scope"
MATCH_PROTECT = "match.protect"
MATCH_SCAN = "match.scan"
MATCH_RESULTS = "match.results"
CARTRIDGE_CALL = "cartridge.call"
CARTRIDGE_SYNC = "cartridge.sync"
