"""Host-clock span around engine.partition(): the hierarchy build."""


def read(rec):
    return rec["spans"].get("build")
