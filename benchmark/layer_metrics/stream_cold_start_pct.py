"""``stream_cold_start_pct``: 100 x pairs dispatched cold / pairs dispatched
in the window, from the deltas of ``stream_frames_cold_start_total`` and
``stream_frames_accepted_total`` (``StreamEngine.report()["counters"]``)."""


def read(run: dict):
    c = run["report"].get("counters", {})
    pairs = c.get("stream_frames_accepted_total")
    if not pairs or "stream_frames_cold_start_total" not in c:
        return None
    return 100.0 * c["stream_frames_cold_start_total"] / pairs
