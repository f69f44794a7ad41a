"""Result transfer: bytes copied from the device to the host per entry
returned over the window (``/stats`` ``transfer``: ``to_host_bytes`` ÷
``entries_returned``); None where the server has no such counter."""


def read(run):
    t = run.stats.get("transfer")
    if not t or not t["entries_returned"]:
        return None
    return t["to_host_bytes"] / t["entries_returned"]
