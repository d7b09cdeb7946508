"""Two counts of a model that drafts with its own multi-token-prediction layer,
over the window (growth of the server's counters between its two scrapes):

``what: "accept_share"``: the drafted tokens the verify accepted, as a share (%)
of those drafted: growth of ``accepted`` over growth of ``drafted`` (both the
``tier="mtp"`` series).
``what: "tokens_per_step"``: the tokens a row emits a verify step it drafted in:
the verify's own token and the accepted drafts, (drafted + accepted) / drafted at
one draft a step = 1.0 + acceptance.

None where ``drafted`` did not grow or is not printed (a server without the tier)."""

from promtext import delta


def read(ctx, params):
    drafted = delta(ctx["m0"], ctx["m1"], params["drafted"])
    if not drafted:
        return None
    accepted = delta(ctx["m0"], ctx["m1"], params["accepted"]) or 0.0
    if params["what"] == "accept_share":
        return 100.0 * accepted / drafted
    return (drafted + accepted) / drafted
