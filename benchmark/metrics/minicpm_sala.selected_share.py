"""Of the keys the queries past ``dense_len`` would attend densely (every key up to
their own, K/V head by K/V head), the share they attended: what the selection leaves of
the attention's work. From the deltas of the engine's ``sparse_keys_attended`` and
``sparse_keys_causal`` (both counted on the device, per call, over the sparse layers)
over the run's load. A program that counts neither, or a load with no such query:
nothing."""


def read(run):
    c = run.get("counters") or {}
    if not c.get("sparse_keys_causal"):
        return None
    return 100.0 * c.get("sparse_keys_attended", 0) / c["sparse_keys_causal"]
