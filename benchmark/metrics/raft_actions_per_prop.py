"""Store actions per raft proposal: the `actions` attribute summed over the
`raft.propose` spans that end inside the window, over their count. One
means every proposal carries a single write."""
import span_records


def read(ctx):
    recs = span_records.window_records(ctx)
    if not recs:
        return None
    counts = [attrs["actions"] for name, *_, attrs in recs
              if name == "raft.propose" and "actions" in attrs]
    if not counts:
        return None
    return sum(counts) / len(counts)
