"""Nemotron-H's training step at its ``tiny`` preset, traced: what
``tests/test_kanana_lm.py::test_nemotron_step_traces_as_before`` compares
with ``tests/fixtures/router_jaxprs/*.txt.gz``, which the same function
wrote from the commit before Nemotron's router moved into
``models/moe_router.py`` (where it was inline in ``LatentMoE``).  The whole
step, float32 and under the bfloat16 policy: a change to the shared router
that moves Nemotron's program fails there.  A remat policy prints as a
function and its address: the address is taken out."""

import re

import jax
import jax.numpy as jnp
import optax

from distributedpytorch_tpu.models import build_model
from distributedpytorch_tpu.parallel import (NEXT_TOKEN, create_train_state,
                                             make_train_step)
from distributedpytorch_tpu.train.precision import precision_policy

PRECISIONS = ("float32", "bfloat16")


def traced(precision: str) -> str:
    policy = precision_policy(precision)
    model = build_model("nemotron_h", lm_config="tiny",
                        dtype=policy.compute_dtype if policy else jnp.float32)
    tx = optax.sgd(1e-2, momentum=0.9)
    tokens = jnp.zeros((2, 16), jnp.int32)
    state = create_train_state(jax.random.PRNGKey(0), model, tx,
                               tokens.shape, input_dtype=jnp.int32)
    step = make_train_step(model, tx, loss_type=NEXT_TOKEN, donate=False,
                           loss_weights=model.loss_weights,
                           precision=policy)
    text = str(jax.make_jaxpr(step)(state, {"tokens": tokens}))
    return re.sub(r" at 0x[0-9a-f]+>", ">", text)
