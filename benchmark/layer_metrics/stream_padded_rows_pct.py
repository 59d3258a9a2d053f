"""``stream_padded_rows_pct``: 100 x zero rows added to fill a batch / rows
dispatched in the window, from the deltas of ``stream_batch_padded_rows_total``
and ``stream_frames_accepted_total`` (``StreamEngine.report()["counters"]``)."""


def read(run: dict):
    c = run["report"].get("counters", {})
    pairs = c.get("stream_frames_accepted_total")
    if not pairs or "stream_batch_padded_rows_total" not in c:
        return None
    padded = c["stream_batch_padded_rows_total"]
    return 100.0 * padded / (pairs + padded)
