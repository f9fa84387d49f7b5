"""Harness span around each wave's deletes and upserts, per Pod replaced
in the window (snapshot feed: cli/serve.py /snapshot/* -> cluster/snapshot.py)."""


def read(run):
    pods = run.spans.get("pods")
    return run.spans["feed_s"] * 1e3 / pods if pods else None
