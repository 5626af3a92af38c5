"""Of the cache slots the engine copied into its calls' padded caches, for
every layer, the share that no query of the call selected: what a gather of
the selected rows through the block table would not move. From the deltas of
the engine's ``sparse_slots_read`` (counted on the device, per call, lane and
layer) and ``sparse_slots_gathered`` (layers x lanes x cache bucket, from the
host's lengths) over the run's load. A program without an indexer counts
neither: nothing."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("sparse_slots_gathered"):
        return None
    return 100.0 * (1.0 - c.get("sparse_slots_read", 0) / c["sparse_slots_gathered"])
