"""Share of the traced sub-window's device time spent getting into and out of
the latent's space: the self time of every operation whose innermost scope is
``extend.attention.latent`` (both down-projections, their norms, ``W_qb``, the
rotations, the absorption of ``W_kvb^K`` into the queries and ``W_kvb^V`` over
the attended latents) / the device's busy time. A program without latent
attention has no such scope: nothing."""

SCOPE = "extend.attention.latent"


def read(run):
    trace = run.get("trace") or {}
    latent = dict(map(tuple, trace.get("ops_by_scope") or [])).get(SCOPE)
    return 100.0 * latent / trace["busy_s"] if latent and trace.get("busy_s") else None
