"""Share of a chip's busy time that its collective operations take: the self
time of the trace's all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all operations over the chip's busy time, mean
over the chips."""


def read(run, params):
    shares = [c["collective_s"] / c["busy_s"]
              for c in run["trace"]["per_chip"] if c["busy_s"] > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
