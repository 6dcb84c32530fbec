"""Mean requests per dispatched batch over the largest batch bucket (the
runtime's ``stats()["mean_batch"]``)."""


def read(r):
    mean, top = r.layer.get("mean_batch"), r.layer.get("max_bucket")
    return None if not mean or not top else mean / top
