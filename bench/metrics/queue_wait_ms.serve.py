"""Mean time a micro-batch's oldest request waited in the queue before its
drain: the ``queue_wait_s`` of ``request.batch`` spans (sample rate 1) in
the window, in ms."""


def read(view):
    waits = [s["attrs"]["queue_wait_s"] for s in view.spans("request.batch")]
    return 1e3 * sum(waits) / len(waits) if waits else None
