"""Wave program: the least HBM traffic the window's work needs, over the
chips' peak HBM bandwidth, over the device busy time.

Bytes counted from the burst's own counts, 4 bytes a word, whatever the
program's shapes: each enqueued record read once from the staged input
and written once to the store; each dequeued record read once from the
store and written once to the reply; each op's descriptor words read
once and its reply words written once."""


def work_bytes(counts: dict, record_words: int, op_words: dict) -> int:
    records = counts["enqueues"] + counts["dequeued"]
    per_op = op_words["descriptor"] + op_words["reply"]
    return 4 * (2 * record_words * records + per_op * counts["ops"])


def read(r):
    if r.trace is None or not r.counts["bursts"]:
        return None
    cfg = r.run.cell.config
    need = work_bytes(r.counts, cfg["record_words"], cfg["op_words"])
    busy_s = r.trace.busy_ns() * 1e-9
    if busy_s <= 0:
        return None
    per_chip = need / r.run.n
    return 100.0 * per_chip / r.peaks["hbm_bytes_per_s"] / busy_s
