"""A new architecture is new files only: a configuration file that names
its own reference module (``reference/<name>.py``) is checked by that
module, and one written in GPTBigCode's published key names resolves to
the program's configuration."""
import json
import os
import sys
import textwrap
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                    "src")]

from servebench import check, run, spec, testing  # noqa: E402

SEED = 2 ** 32 + 515
#: every position's best logit is token 0's, 1000 above all others
STUB = '''
"""A stand-in reference: token 0 always leads the others by 1000."""
import jax.numpy as jnp


class Reference:
    def __init__(self, config, seed, bits=8):
        self.vocab = config["vocab_size"]

    def hidden(self, tokens):
        return jnp.ones(tokens.shape + (1,), jnp.float32)

    def output_table(self):
        return jnp.zeros((self.vocab, 1), jnp.float32).at[0].set(1000.0)


def row_logits(rows, table):
    yield 0, rows @ table.T
'''


def _layout(tmp_path):
    layout = testing.smoke_layout(str(tmp_path), dict(
        testing.TINY_QWEN, reference="stub"), loop="closed")
    ref_dir = tmp_path / "bench" / "reference"
    ref_dir.mkdir()
    (ref_dir / "stub.py").write_text(textwrap.dedent(STUB))
    return spec.Layout(root=layout.root, bench_dir=layout.bench_dir,
                       reference_dir=str(ref_dir))


def test_compare_uses_the_reference_the_configuration_names(tmp_path):
    layout = _layout(tmp_path)
    cell = spec.load_cell("tiny-closed", layout)
    served = [(np.arange(5, dtype=np.int32), np.array([3, 0, 9]))]
    nums = check.compare(cell.config, SEED, served,
                         reference_dir=layout.reference_dir)
    assert nums == {"widest_gap": 1000.0, "served_tokens": 3}
    # without the key, the configuration keeps reference/model.py
    assert check.reference_module(testing.TINY_QWEN).__file__ == \
        os.path.join(BENCH, "reference", "model.py")


def test_a_run_is_checked_by_the_named_reference(tmp_path, capsys):
    rc = run.main(["--workload", "tiny-closed", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"],
                  started=time.monotonic(), layout=_layout(tmp_path),
                  require_chip=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] == 1000.0


def test_gptbigcode_key_names_resolve(tmp_path):
    config = {"name": "tiny-bigcode", "source": "test",
              "registry": "granite-20b", "n_embd": 64, "n_inner": 256,
              "n_layer": 2, "n_head": 4, "multi_query": True,
              "vocab_size": 277, "layer_norm_epsilon": 1e-05,
              "torch_dtype": "float32",
              "reference": "bigcode"}
    layout = testing.smoke_layout(str(tmp_path), config)
    cfg = spec.model_config(spec.load_cell("tiny-open", layout))
    assert (cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.n_heads,
            cfg.n_kv_heads, cfg.hd, cfg.vocab_size) == (
        64, 256, 2, 4, 1, 16, 277)
    assert cfg.norm_eps == 1e-05 and cfg.pim_mode == "quant"
