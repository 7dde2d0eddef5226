"""The ``work`` contract of ``second_arch.build_renamed_decode``: the
zoo's OPT-style block priced by ``benchmarks/harness/flops.py``, read
through this builder's own argument names."""

from benchmarks.harness import flops


def _zoo_names(config: dict) -> dict:
    kw = config["builder_kwargs"]
    return {"builder_kwargs": {
        "vocab": kw["n_tokens"], "num_layers": kw["depth"],
        "hidden": kw["width"], "ff_dim": kw["inner"]}}


def trained_token_flops(config: dict, seq_len: int) -> float:
    return flops.trained_token_flops(_zoo_names(config), seq_len)


def attention_kernel_flops(config: dict, batch: int, seq_len: int) -> float:
    return flops.attention_kernel_flops(_zoo_names(config), batch, seq_len)


def served_token_flops(config: dict, context, logits: bool = True):
    return flops.served_token_flops(_zoo_names(config), context, logits)


def cached_token_bytes(config: dict, itemsize: int) -> int:
    return flops.cached_token_bytes(_zoo_names(config), itemsize)
