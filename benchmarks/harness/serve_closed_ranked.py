"""Runner kind `serve_closed_ranked`: `serve_closed`, its window and its
numbers untouched, with a SECOND limit in the comparison that decides
`correct`: the `check.request_rank`-th smallest, over the sampled requests,
of a request's widest logit gap, held to `check.rank_gap_limit` (beside
`check.gap_limit` on the widest gap of all).

What it is for: a decoder whose rare rows move whole. An expert layer that
holds a share of the experts flips a chosen expert on a near tie between
bfloat16 and float32, and a flipped HELD expert is present or absent whole;
the widest gap of all is then set by the one such row a sample meets, and
reads what a run whose EVERY row is a little wrong reads (the float8
control). The requests tell the two apart: a sound run leaves most of its
requests with next to no gap and a few with a wide one, the control leaves
next to none without. So the number is a LOW rank of the requests' gaps:
it moves only when most requests have a wide gap, whatever the widest is.
`serving.check_tokens` already returns a request's widest gap
(`per_request`, and `control_per_request` under `tools/control.py`); this
runner reads them and adds no pass of the reference.

`correct` is the widest gap within `gap_limit` AND the ranked gap within
`rank_gap_limit`; either alone refuses a run.
"""
from benchmarks.harness import common, serving


def ranked_gap(per_request, rank: int) -> float:
    """The `rank`-th smallest of the requests' widest gaps (the largest of
    fewer than `rank` requests); infinite for no request."""
    if not per_request:
        return float("inf")
    return sorted(per_request)[min(rank, len(per_request)) - 1]


def run(ctx):
    rec = serving.run(ctx, "closed")
    check, limits = rec.stamps["check"], ctx["traffic"]["check"]
    rank, limit = int(limits["request_rank"]), float(limits["rank_gap_limit"])
    per_request = check.get("per_request", ())
    check["ranked_gap"] = ranked_gap(per_request, rank)
    within = check["ranked_gap"] <= limit
    shown = dict(number="ranked_request_gap", rank=rank,
                 value=check["ranked_gap"], limit=limit,
                 per_request=sorted(round(g, 4) for g in per_request))
    if "control_per_request" in check:
        check["control_ranked_gap"] = ranked_gap(
            check["control_per_request"], rank)
        shown["control"] = check["control_ranked_gap"]
    rec.correct = bool(rec.correct and within)
    common.say("check", **shown, correct=within)
    return rec
