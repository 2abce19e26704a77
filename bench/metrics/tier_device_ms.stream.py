"""Mean dispatch-to-done time of the tier's device leg (host to device,
kernel, device to host) over the window, from the program's
``serve_device_seconds`` span histogram (sum over count)."""


def read(run):
    total, count = run["spans"]["serve_device_seconds"]
    return total / count * 1e3 if count else None
