"""Mean per round of window wall that is not in the train phase: FedAvg on
the host path is inside train; this is validation, the wait for the
previous checkpoint, journals and the loop's own work."""


def read(run):
    rounds = run["window_rounds"]
    if not rounds:
        return None
    train = sum(rec["phases"]["train"]["total_s"] for rec in rounds)
    return 1e3 * (run["window_s"] - train) / len(rounds)
