"""Mean body decode time of the HTTP ingress over the window, from the
program's ``ingress_decode_seconds`` histogram (sum over count)."""


def read(run):
    total, count = run["spans"]["ingress_decode_seconds"]
    return total / count * 1e3 if count else None
