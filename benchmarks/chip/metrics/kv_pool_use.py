"""Block manager: time-mean, over the window's engine steps, of the share of
the KV pool's blocks not free (%). Sampled before each step in traced runs."""


def read(rec):
    v = rec.get("counts", {}).get("kv_pool_use")
    return None if v is None else 100.0 * v
