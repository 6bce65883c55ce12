"""Pool: mean share of the pod pool's pages in use (a program counter
read after every step of the window), in
percent."""


def read(record):
    t0, t1 = record["window"]
    xs = [used / total for a, b, used, total in record["steps"]
          if t0 <= a <= t1 and total]
    return 100.0 * sum(xs) / len(xs) if xs else None
