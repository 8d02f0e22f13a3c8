"""Of the decode steps dispatched, the share dispatched while an earlier
step's tokens were still unread in the scheduler's ring, in percent
(``ServingMetrics.snapshot()``: ``decode_overlap_share``).  Near 100 in a
steady window of the ring, 0 on the sync and speculative bodies; a program
without the counter, as a parent commit is, gives nothing."""
META = {"source": "program_counter"}


def read(run):
    share = (run.serve or {}).get("snapshot", {}).get("decode_overlap_share")
    return None if share is None else 100.0 * share
