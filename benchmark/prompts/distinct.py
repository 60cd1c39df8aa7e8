"""Prompts that share nothing: every token id drawn from the seed. A mix
names where its prompts' content comes from by the name of a module here
(`"prompts": "distinct"`); one with shared prefixes is another module."""
import numpy as np


def token_ids(mix: dict, rng, lengths, vocab: int) -> list:
    """One int64 array of ids per length, in the order given."""
    return [rng.integers(0, vocab, size=int(n), dtype=np.int64)
            for n in lengths]
