"""Train phase wall over window wall, from the program's round records
(``phases`` of each kind=round record)."""


def read(run):
    train = sum(rec["phases"]["train"]["total_s"]
                for rec in run["window_rounds"])
    return 100.0 * train / run["window_s"] if run["window_s"] else None
