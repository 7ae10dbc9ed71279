"""Share (%) of the batcher's row capacity that carried a request: rows
dispatched over batches times the server's ``max_batch``, from ``stats``."""


def read(artifacts):
    serve = artifacts.get("serve")
    if not serve:
        return None
    req = serve["stats"]["requests"]
    if not req["batches"]:
        return None
    return 100.0 * req["rows"] / (req["batches"] * req["max_batch"])
