"""Mean queries per device batch, from the batcher's occupancy histogram
(`es_batch_occupancy_count_total{size=...}`) over the window."""


def read(ctx, params):
    name = params["metric"]
    before = {lab.get("size"): v
              for lab, v in ctx["before"]["metrics"].get(name, [])}
    batches = queries = 0.0
    for lab, v in ctx["after"]["metrics"].get(name, []):
        n = v - before.get(lab.get("size"), 0.0)
        batches += n
        queries += n * float(lab["size"])
    return queries / batches if batches else None
