"""Model step, decode: the least time the chip could take for the decode
steps' work (the larger of FLOPs / peak FLOP/s and bytes / HBM bandwidth;
the bytes are every weight once plus the live KV of live sequences, and
they bound it) over the decode program's device time, in %."""

from bench.reading import decode_work


def read(obs):
    w = decode_work(obs)
    if w is None:
        return None
    _, least, dev_s = w
    return least / dev_s * 100.0
