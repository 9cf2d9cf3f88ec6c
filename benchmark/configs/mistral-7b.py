"""Hook of the ``mistral-7b`` configuration: ``zoo:transformer_lm`` passes no
``d_ff`` to ``init_params`` (models/zoo.py), so the published FFN width of
14336 is reachable only through a factory of the configuration's own,
registered through the public ``zoo.model_factory`` decorator. Nothing but
``tfm.init_params`` and a ``ZooModel``: the server needs ``params`` only."""

import jax

from nnstreamer_tpu.models import transformer as tfm
from nnstreamer_tpu.models import zoo


@zoo.model_factory("bench_mistral_lm")
def _bench_mistral_lm(**options) -> zoo.ZooModel:
    n_heads = int(options["n_heads"])
    params = tfm.init_params(
        jax.random.PRNGKey(int(options.get("seed", 0))),
        int(options["vocab"]), int(options["d_model"]), n_heads,
        int(options["n_layers"]), d_ff=int(options["d_ff"]),
        n_kv_heads=int(options["n_kv_heads"]),
    )

    def fn(tokens):
        return tfm.apply(params, tokens, n_heads)

    return zoo.ZooModel("bench_mistral_lm", fn, None, params)
