"""A word-level tokenizer with one printable word per id.

A deployment serves the model's own tokenizer, in which every token is
text.  Without a network there is none, and the program's byte fallback
turns ids above 255 into the empty string, so a client would see almost
no token of a 200k-row head.  This builds the stand-in once per
checkout: id ``i`` is the word ``w<i>``, split on whitespace, with no
special token at all (an added token such as ``w2`` would split
``w200000``).  A prompt of n words is n tokens, and every generated
token reaches the client as a non-empty piece of text that names its id.
"""

import json
import os

from paths import CACHE


def tokenizer_dir(vocab: int) -> str:
    """The directory for this vocabulary, built if it is not there."""
    path = os.path.join(CACHE, f"tokenizer_w{vocab}")
    done = os.path.join(path, "tokenizer_config.json")
    if os.path.exists(done):
        return path
    from tokenizers import Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    tok = Tokenizer(models.WordLevel(
        vocab={f"w{i}": i for i in range(vocab)}, unk_token="w0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(os.path.join(path, "tokenizer.json"))
    tmp = done + ".tmp"
    with open(tmp, "w") as f:       # written last: marks the directory whole
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "unk_token": "w0",
                   "clean_up_tokenization_spaces": False,
                   "model_max_length": 1 << 30}, f)
    os.replace(tmp, done)
    return path
