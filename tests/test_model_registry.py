"""``ray_tpu.models.FAMILIES`` is the one table of families: what
``LLMConfig`` builds, what the engine builds from it and what the config
refuses are all read off it. And what left the product (a speculative
draft) is refused aloud, by the name of the field or parameter."""

import importlib

import jax
import pytest

from ray_tpu import models
from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import ContinuousBatchingEngine
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.parallel.sharding import unbox_params

FEATURES = {"adapters", "mesh", "prefill_chunk"}  # what a family may refuse


@pytest.mark.parametrize("name", list(models.FAMILIES))
def test_a_family_is_its_entry_of_the_table(name):
    built = LLMConfig(
        model_id=f"{name}-tiny", model_family=name).build_model_config()
    assert type(built) is models.config_type(name)
    module = importlib.import_module(f"ray_tpu.models.{name}")
    assert models._family(built) is module
    assert callable(module.build) and callable(module.init_params)
    refused = set(models.refusals(name))
    assert refused <= FEATURES
    assert (not refused) == (name == "llama")


def test_an_unknown_family_is_told_the_names_of_the_table():
    for ask in (models.config_type, models.refusals,
                lambda name: LLMConfig(model_family=name)):
        with pytest.raises(ValueError, match="unknown model family") as err:
            ask("mamba")
        assert all(name in str(err.value) for name in models.FAMILIES)
    with pytest.raises(TypeError, match="no model family for a dict"):
        models.build({})


@pytest.mark.parametrize("field,value", [
    ("draft_model", "llama-tiny"),
    ("draft_model_kwargs", {"n_layers": 1}),
    ("spec_tokens", 4),
])
def test_a_config_that_names_a_draft_is_refused_by_field(field, value):
    with pytest.raises(TypeError, match=field):
        LLMConfig(**{field: value})


@pytest.mark.parametrize("parameter", ["draft", "spec_tokens"])
def test_an_engine_that_is_handed_a_draft_is_refused_by_parameter(parameter):
    cfg = LlamaConfig.tiny(max_seq_len=32, n_layers=1)
    params = unbox_params(init_params(cfg, jax.random.PRNGKey(0)))
    value = (cfg, params) if parameter == "draft" else 3
    with pytest.raises(TypeError, match=parameter):
        ContinuousBatchingEngine(cfg, params, **{parameter: value})
