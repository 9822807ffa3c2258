"""verify.memo_share: the share of layers whose facts the layer memo
replayed (``Report.memo``: memo_hits over layers), in %, over the traced
window's verdicts."""


def read(run):
    memos = [r.memo for r in run.data.get("reports", ()) if r.memo and r.memo.layers]
    if not memos:
        return None
    return 100.0 * sum(m.memo_hits for m in memos) / sum(m.layers for m in memos)
