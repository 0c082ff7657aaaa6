"""The in-mesh simulation examples that train a model split between the clients
and the server (FedGKT, SplitNN, vertical FL), each run end to end on tiny
synthetic data as ``tests/test_examples.py`` runs the rest."""

import os

import pytest

from tests.test_examples import _simulation_configs, run_simulation_example

pytestmark = pytest.mark.heavy  # e2e rounds + XLA compiles; see pytest.ini


@pytest.mark.parametrize(
    "cfg", _simulation_configs("split"), ids=lambda p: p.split(os.sep)[-2]
)
def test_simulation_example(cfg):
    run_simulation_example(cfg)
