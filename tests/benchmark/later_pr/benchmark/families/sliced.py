"""Family ``sliced`` (a fixture: tests/benchmark/later_pr/README.txt): a
stack of feed-forward layers with one wider mixing layer every
``global_every``, on a chip that holds a slice of the vocabulary. Not a
model anybody publishes: it is here for its shape, which the decoder's
is not: a tree three levels deep, two stacked groups of different
leading lengths, two keys that may be cut, and needed bytes of its own.
It imports nothing of the program, so it gives no engine and no loss."""

import types

widths = ("hidden_size", "intermediate_size", "global_every")
reducible = {"num_hidden_layers": 2, "vocab_size": 64}


def rehearsal_config():
    return {"hidden_size": 32, "intermediate_size": 64, "global_every": 2,
            "num_hidden_layers": 6, "vocab_size": 128}


def split(config):
    """(local layers, global layers): every ``global_every``-th is global."""
    n_global = config["num_hidden_layers"] // config["global_every"]
    return config["num_hidden_layers"] - n_global, n_global


def param_layout(config):
    d, ff, v = (config["hidden_size"], config["intermediate_size"],
                config["vocab_size"])
    n_local, n_global = split(config)
    return {
        ("embed",): ((v, d), 0.02, False),
        ("head", "norm"): ((d,), None, False),
        ("head", "out"): ((d, v), d ** -0.5, False),
        ("local", "mlp", "up"): ((n_local, d, ff), d ** -0.5, True),
        ("local", "mlp", "down"): ((n_local, ff, d), ff ** -0.5, True),
        ("local", "norm"): ((n_local, d), None, True),
        ("global", "mix"): ((n_global, d, d), d ** -0.5, True),
    }


def program_config(config, *, training, control=False):
    return types.SimpleNamespace(
        program="sliced", training=training, control=control,
        layers=split(config), width=config["hidden_size"])


def step_bytes(config, resident_tokens, bytes_per_param=2):
    """Every layer's weights and the head's slice once; only the global
    layers keep anything per resident token."""
    d, ff, v = (config["hidden_size"], config["intermediate_size"],
                config["vocab_size"])
    n_local, n_global = split(config)
    weights = n_local * 2 * d * ff + n_global * d * d + d * v
    return (weights * bytes_per_param
            + resident_tokens * n_global * d * bytes_per_param)


needed = types.SimpleNamespace(decode_step_bytes=step_bytes)
