"""Share of the train phase's wall in which the host builds and uploads
the next batch: the gaps between one compiled-step call's return and the
next call's entry inside a column chunk, from the benchmark's own spans
around the step (the program's ``timings["host_data_s"]`` exists only on
its device-resident path; the one-chip path reports none)."""


def read(run):
    calls = sorted(c for c in run["tap_calls"]
                   if run["t_open"] <= c[0] <= run["t_close"])
    feed = sum(nxt[0] - cur[1] for cur, nxt in zip(calls, calls[1:])
               if not nxt[2])
    train = sum(rec["phases"]["train"]["total_s"]
                for rec in run["window_rounds"])
    return 100.0 * feed / train if train else None
