"""Ingest layer: host clock around ``shard_rows`` of the device-born
table and labels plus ``block_until_ready``, in set-up."""


def read(ctx):
    return ctx["counters"]["ingest_s"]
