"""The one general traffic generator. A traffic mix is a data file
under `benchmark/traffic/`; everything here is driven by its
parameters and by `--seed`, and the program sees only what comes out.

Every seed offers the same work: a training stream differs in its
tokens only, and a serving mix keeps one schedule of arrivals and
lengths (its traffic file's `schedule_seed`) and differs in the
prompts' tokens.
"""

from statistics import NormalDist

import numpy as np

def rng_for(seed, stream):
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed), int(stream)]))


# ----------------------------------------------------------------------
# tokens
# ----------------------------------------------------------------------
class TokenSource:
    """Tokens over `vocab`: "zipf" (rank r drawn with weight
    r**-exponent, ranks laid over the vocabulary by a seeded
    permutation, so there is something to learn) or "uniform"."""

    def __init__(self, spec, vocab, seed):
        self.rng = rng_for(seed, 11)
        self.vocab = vocab
        self.kind = spec.get("dist", "uniform")
        if self.kind == "zipf":
            w = np.arange(1, vocab + 1, dtype=np.float64) \
                ** -float(spec["exponent"])
            self.cdf = np.cumsum(w / w.sum())
            self.perm = rng_for(seed, 12).permutation(vocab)
        elif self.kind != "uniform":
            raise ValueError(f"unknown token distribution {self.kind!r}")

    def draw(self, shape):
        if self.kind == "uniform":
            return self.rng.integers(0, self.vocab, shape, dtype=np.int32)
        ranks = np.searchsorted(self.cdf, self.rng.random(shape))
        return self.perm[np.minimum(ranks, self.vocab - 1)] \
            .astype(np.int32)


def train_batches(spec, vocab, gas, rows, seq, seed):
    """Endless stream of {"input_ids": int32 [gas, rows, seq]}; every
    row of every batch differs."""
    src = TokenSource(spec["tokens"], vocab, seed)
    while True:
        yield {"input_ids": src.draw((gas, rows, seq))}


# ----------------------------------------------------------------------
# lengths
# ----------------------------------------------------------------------
def quantile_lengths(spec, n):
    """The (i + 1/2)/n quantiles of a length distribution, as whole
    numbers clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    elif kind == "uniform":
        v = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


# ----------------------------------------------------------------------
# arrivals
# ----------------------------------------------------------------------
def arrival_times(spec, start, length, rng):
    """Sorted arrival times in [start, start + length). The count is
    round(rate * length) for every process and every seed."""
    n = int(round(spec["rate_per_s"] * length))
    kind = spec.get("process", "poisson_conditioned")
    if kind == "poisson_conditioned":
        # a Poisson process given its count: sorted uniform times
        t = np.sort(rng.random(n)) * length
    elif kind == "jittered_grid":
        # one arrival in each interval of 1/rate, at a uniform offset
        t = (np.arange(n) + rng.random(n)) * (length / max(n, 1))
    elif kind == "gamma":
        # renewal process with the given coefficient of variation,
        # scaled to fill the span with exactly n arrivals
        shape = 1.0 / float(spec["cv"]) ** 2
        gaps = rng.gamma(shape, 1.0, n + 1)
        t = np.cumsum(gaps)[:n] / gaps.sum() * length
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return start + t


def serve_requests(spec, vocab, seconds, seed, tail_s=0.0):
    """The requests of one run, sorted by arrival: dicts with `rid`,
    `arrival_s` (0 = the window opens; the pre-roll's are negative),
    `tokens` (the prompt) and `max_new_tokens`. With `tail_s` the
    schedule goes on for that long after the window (rids `t<n>.<i>`,
    their tokens drawn after all others, so the window's requests are
    the same with and without a tail): a traced run profiles there.

    The schedule (arrival times, and which prompt and answer lengths
    arrive when) is drawn from `arrivals.schedule_seed`, which the
    traffic file fixes; `--seed` draws the prompts' tokens (and, in the
    harness, the weights) and moves each arrival by up to
    `arrivals.seed_jitter_s` either way, so that seeds differ in where
    arrivals fall within a serving iteration and in nothing else (with
    no jitter every run replays the same phases to 0.2%, and a change
    that shifts the loop's timing by a hair redraws them all at once).
    With the whole schedule drawn from `--seed`,
    runs of one seed agreed to 1% and seeds differed by 5-6%: in a
    window of 28 requests, where the two or three longest answers land
    decides what is delivered inside it (PERF.md, PR 23). The lengths
    are the (i + 1/2)/N quantiles of their distributions, paired by
    two shuffles. The pre-roll repeats the window's own last `preroll_s`
    seconds (same lengths, fresh tokens), one window earlier: the
    traffic is periodic in the window, so what spills into the window
    at its start is what spills out of it at its end, and the work done
    inside it does not depend on which requests straddle its edges."""
    arr = spec["arrivals"]
    seconds, preroll = float(seconds), float(arr["preroll_s"])
    tokens = TokenSource(spec.get("tokens", {}), vocab, seed)
    rng = rng_for(arr["schedule_seed"], 20)
    times = arrival_times(arr, 0.0, seconds, rng)
    n = len(times)
    jitter = float(arr.get("seed_jitter_s", 0.0))
    moved = np.clip(times + rng_for(seed, 21).uniform(-jitter, jitter, n),
                    0.0, np.nextafter(seconds, 0.0))
    prompts = rng.permutation(quantile_lengths(spec["prompt_tokens"], n))
    answers = rng.permutation(quantile_lengths(spec["output_tokens"], n))
    cap = int(spec["max_total_tokens"])
    out = []
    for period in range(int(-(-preroll // seconds)) + 1):
        for i in range(n):
            # the schedule, not the jitter, decides who is in the pre-roll
            if float(times[i]) - period * seconds < -preroll:
                continue
            out.append({"rid": f"w{i}" if period == 0 else f"p{period}.{i}",
                        "arrival_s": float(moved[i]) - period * seconds,
                        "tokens": tokens.draw(
                            (int(min(prompts[i], cap - answers[i])),)),
                        "max_new_tokens": int(answers[i])})
    for period in range(1, int(-(-float(tail_s) // seconds)) + 1):
        for i in range(n):
            if float(times[i]) + (period - 1) * seconds < tail_s:
                out.append({"rid": f"t{period}.{i}",
                            "arrival_s": float(moved[i]) + period * seconds,
                            "tokens": tokens.draw(
                                (int(min(prompts[i], cap - answers[i])),)),
                            "max_new_tokens": int(answers[i])})
    out.sort(key=lambda r: r["arrival_s"])
    return out
