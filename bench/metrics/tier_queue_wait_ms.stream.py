"""Mean enqueue-to-flush wait of the serving tier over the window, from
the program's ``serve_queue_wait_seconds`` span histogram (sum over
count)."""


def read(run):
    total, count = run["spans"]["serve_queue_wait_seconds"]
    return total / count * 1e3 if count else None
