"""Rows per server flush over the flush's batch shape (%), from the
server's own counters (``engine_queries`` / ``engine_batches``) over
the window."""


def read(ctx):
    sw = ctx.get("server_window")
    if not sw or not sw["engine_batches"]:
        return None
    return 100.0 * sw["engine_queries"] / (sw["engine_batches"]
                                           * sw["batch_size"])
