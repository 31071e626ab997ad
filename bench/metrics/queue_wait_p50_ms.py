"""Median time from a request's arrival to its admission into a slot
(``Request.queue_wait_s``) over the window's requests, in ms."""
import reduce


def read(ctx):
    return reduce.median_ms(ctx["queue_wait_s"])
