"""A percentile of (bind stamp - due), in milliseconds, over the pods that
were due inside the window. The due stamp is the client's; the bind stamp
is the harness's own, taken where the store binds (`harness.bind_ledger`).
A pod that never bound counts as +inf.

selector: {"percentile": 0..100}
"""

from harness import stats


def window_pods(run) -> list:
    """[(due ns, bind ns or None), ...] for the arrivals due in the window,
    joined on the uids the client's report gives them."""
    bound_at = run.bound_at
    return [
        (due, bound_at.get(uid))
        for uid, due in zip(run.client["window_uids"],
                            run.client["window_due_ns"])
    ]


def samples_ms(run) -> list:
    return [
        float("inf") if at is None else (at - due) / 1e6
        for due, at in window_pods(run)
    ]


def read(selector: dict, run):
    return stats.percentile(samples_ms(run), selector["percentile"])
