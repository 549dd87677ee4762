"""Graph identity: every tracer entry point emits a fixed graph.

Each served device program is `exec.executable` jitting a traced graph,
and each cycle record in results/*.json is `lower` + `schedule` of one, so
a graph that stays node-for-node identical keeps the chip programs, the
stream-cache keys and the cycle records where they are.  The fingerprint
covers every node (op, inputs, shape, dtype, attrs, tag) in emission order
plus the graph's name maps; tags are part of it because
repro.npec.schedule and repro.npec.fleet.partition parse them.

A change that means to move a graph updates the hashes it moves here, in
the same commit, and says why.
"""
import hashlib

import pytest

from repro import npec
from repro.configs import get_config
from repro.core.cycles import BertShape


def fingerprint(g) -> str:
    h = hashlib.sha256()
    for n in g.nodes:
        h.update(repr((n.op, n.inputs, n.shape, n.dtype,
                       sorted(n.attrs.items()), n.tag)).encode())
    for m in (g.inputs, g.outputs, g.caches, g.cache_updates,
              g.kv_exports):
        h.update(repr(m).encode())
    return h.hexdigest()


def _ring_decode(name, batch):
    cfg = get_config(name)
    return npec.trace_decode(cfg, cfg.window, layers=2, batch=batch,
                             window=True)


CASES = {
    # trace_model: the paper's encoder and the prefill-only families
    "model-bert-64": lambda: npec.trace_model(
        get_config("bert_base"), 64, layers=2),
    "model-bert-64-noembed": lambda: npec.trace_model(
        get_config("bert_base"), 64, layers=2, include_embed=False),
    "model-bert-smoke-gqa": lambda: npec.trace_model(
        get_config("bert_base", smoke=True), 16, layers=2),
    "model-glm4-64": lambda: npec.trace_model(
        get_config("glm4_9b"), 64, layers=2),
    "model-granite-16": lambda: npec.trace_model(
        get_config("granite_moe_1b_a400m"), 16, layers=2),
    "model-llama4-16": lambda: npec.trace_model(
        get_config("llama4_maverick_400b_a17b"), 16, layers=2),
    # trace_prefill, whole prompt
    "prefill-bert-64": lambda: npec.trace_prefill(
        get_config("bert_base"), 64, layers=2),
    "prefill-bert-512": lambda: npec.trace_prefill(
        get_config("bert_base"), 512, layers=2),
    "prefill-glm4-512": lambda: npec.trace_prefill(
        get_config("glm4_9b"), 512, layers=2),
    "prefill-bert-smoke-gqa": lambda: npec.trace_prefill(
        get_config("bert_base", smoke=True), 16),
    "prefill-starcoder2-window": lambda: npec.trace_prefill(
        get_config("starcoder2_3b"), 64, layers=2, window=True),
    "prefill-starcoder2-smoke-window-noembed": lambda: npec.trace_prefill(
        get_config("starcoder2_3b", smoke=True), 16, layers=2,
        include_embed=False, window=True),
    # trace_prefill, one slice over the cache banks
    "slice-bert-16-64": lambda: npec.trace_prefill(
        get_config("bert_base"), 16, layers=2, cache_len=64),
    "slice-bert-smoke-gqa": lambda: npec.trace_prefill(
        get_config("bert_base", smoke=True), 16, layers=2, cache_len=64),
    "slice-glm4-16-64": lambda: npec.trace_prefill(
        get_config("glm4_9b"), 16, layers=2, cache_len=64),
    "slice-glm4-16-64-noembed": lambda: npec.trace_prefill(
        get_config("glm4_9b"), 16, layers=2, cache_len=64,
        include_embed=False),
    "slice-starcoder2-window": lambda: npec.trace_prefill(
        get_config("starcoder2_3b"), 16, layers=2, cache_len=64, window=True),
    # trace_decode
    "decode-bert-b1": lambda: npec.trace_decode(
        get_config("bert_base"), 512, layers=2),
    "decode-bert-b2": lambda: npec.trace_decode(
        get_config("bert_base"), 512, layers=2, batch=2),
    "decode-bert-smoke-gqa-b2": lambda: npec.trace_decode(
        get_config("bert_base", smoke=True), 64, layers=2, batch=2),
    "decode-glm4-smoke-b2": lambda: npec.trace_decode(
        get_config("glm4_9b", smoke=True), 32, batch=2),
    "decode-glm4-b1": lambda: npec.trace_decode(
        get_config("glm4_9b"), 2048, layers=2),
    "decode-glm4-b1-noembed": lambda: npec.trace_decode(
        get_config("glm4_9b"), 2048, layers=2, include_embed=False),
    "decode-glm4-b8": lambda: npec.trace_decode(
        get_config("glm4_9b"), 2048, layers=2, batch=8),
    "decode-glm4-b8-noembed": lambda: npec.trace_decode(
        get_config("glm4_9b"), 2048, layers=2, batch=8, include_embed=False),
    "decode-glm4-b8-ring": lambda: npec.trace_decode(
        get_config("glm4_9b"), 512, layers=2, batch=8, window=True),
    "decode-starcoder2-window": lambda: _ring_decode("starcoder2_3b", 1),
    "decode-starcoder2-window-b2": lambda: _ring_decode("starcoder2_3b", 2),
    # one MoE block with its routing intermediates
    "moe-block-granite": lambda: npec.trace_moe_block(
        get_config("granite_moe_1b_a400m"), 16, debug_outputs=True),
    "moe-block-llama4": lambda: npec.trace_moe_block(
        get_config("llama4_maverick_400b_a17b"), 16, layer=1,
        debug_outputs=True),
    # the dims-only builders core.cycles costs
    "shape-bert-128": lambda: npec.trace_bert_shape(
        BertShape(seq=128), layers=2),
    "shape-slice-64-512": lambda: npec.trace_prefill_slice_shape(
        BertShape(), 512, 64, layers=2),
    "shape-decode-b1": lambda: npec.trace_decode_bert_shape(
        BertShape(), 512, layers=2),
    "shape-decode-b4": lambda: npec.trace_decode_bert_shape(
        BertShape(), 512, layers=2, batch=4),
    "shape-decode-b4-ring": lambda: npec.trace_decode_bert_shape(
        BertShape(), 512, layers=2, batch=4, window=True),
}

# Computed on the tree before the tracer became one stack walk over one
# attention emitter; that rewrite had to leave every graph as it was.
EXPECTED = {
    "decode-bert-b1":
        "7e529726153ab6b767a0f1b21afe43bdbb58dc5f2c3413f4881f2e991e19703a",
    "decode-bert-b2":
        "1a120ae2ef0e3a3904a8377fc7ddcccd8f8209bab9d60ebe472350d7fb2defb1",
    "decode-bert-smoke-gqa-b2":
        "2f897e58f6bcc5f141fac046403a492dcbdb54bddbcd2e9d473511c6f94473fd",
    "decode-glm4-b1":
        "25aee5f66da8ce4b641583b0d1421cc544e8a22b84a3ed41c365ebfe1734f9d8",
    "decode-glm4-b1-noembed":
        "3c559a3006e86e11c1a2cd69986557101607f062cb35e351b02aa77c8922feb0",
    "decode-glm4-b8":
        "fbddab9596f1566707b81c055b2e96330b34cd0af51a4c42022826de72dd98a3",
    "decode-glm4-b8-noembed":
        "1acc51c6270aae2462e8990d06fda053723de3c5e0da4499a227947b30e3c5f8",
    "decode-glm4-b8-ring":
        "56a922e551ce93723ecdd9e7f7af4a56c5c7c1639f45c8e832498302696f9d70",
    "decode-glm4-smoke-b2":
        "d6ab3e01c6d9102894d9dda258c1a4a9605f4818e9f068d4acbd669b8fcd71a8",
    "decode-starcoder2-window":
        "bfd2552421a11ab8f54e5f6c46422b28bd8b0e4e4893ea4ae8de8d8f5a995da2",
    "decode-starcoder2-window-b2":
        "67a4d77b6fd7576f460a5588436c36546ed3d4f400902c8019d8d1554a9c8166",
    "model-bert-64":
        "91a29460806f1bc272706e35b4017008f704b2280029406b1eaf2946576db187",
    "model-bert-64-noembed":
        "5a99653e4b1d617f80bf6cf6af8182c09ed6393b3f2466ac6beb61b992e41cc6",
    "model-bert-smoke-gqa":
        "1e2f0b13a5229009b472bfe28ece4302dcaa3da2352cede4ebb7d9e843a4b76b",
    "model-glm4-64":
        "f66f9883584685710d191a57424a86602966515a6c3e96f0e094edf1b22c47ee",
    "model-granite-16":
        "51a4c23cf000ca426a90437be3e05ec12435d9a56129e7d62cce77b1c6e4be1d",
    "model-llama4-16":
        "e41319826ec838ea0e3494592eccd494fe4a330bf7c64a9f8b19fe819331412b",
    "moe-block-granite":
        "20c92c1fb8c6005dea67a987d5e561b93842c48c47a6fbbb886aef5de9ad0ab6",
    "moe-block-llama4":
        "1d6a5a7545625826c24b78ac3a346247c29a7883d94930ddfb100e898a6be5a5",
    "prefill-bert-512":
        "e092b26927efa0621ce3405d15d8be0595c335be920ea59d35596ecdae7bfd90",
    "prefill-bert-64":
        "a66836491304e20da56286b9574a5bea8c62b205a82d2a44cee5c1255eb81566",
    "prefill-bert-smoke-gqa":
        "01b6bd239fb875ce5709fb7c55468959d32c7ac2ad13afedaafbce47093c1823",
    "prefill-glm4-512":
        "a31419bcf2384b1cf1a81c598147d163b0b1b7ea98db8868056cb35d1b4e0cae",
    "prefill-starcoder2-smoke-window-noembed":
        "fcae43028d07898988663d558d3e9977809800b0811052de7226dcc43c4a89f0",
    "prefill-starcoder2-window":
        "187574c158d8ec203608e8750522fd31851a8c4796bd82891a8f45c9c7caea03",
    "shape-bert-128":
        "31af6fe8eb9d517a60251da0e4370597c28de6cd10efab933ad2dd6d95746d5c",
    "shape-decode-b1":
        "d9cac9b2e8810dca426f92201c212ac0d52c19b0521b95e4859c4dfb9e20a1b3",
    "shape-decode-b4":
        "49e823c5c1f532a97122db16be395185a4a764cf56c97613711956746d601d46",
    "shape-decode-b4-ring":
        "e4cd74199f8f92b642047e02294f4f37129c1670f3b188597798408fe1147a3e",
    "shape-slice-64-512":
        "fade70fa2977893104190da73892c6b675bbc1aa949dfd2ad186e83f6b39fda9",
    "slice-bert-16-64":
        "d37eb4222306b443c00cdef33c3f407871adbbf6ff480250cc4b070a20048690",
    "slice-bert-smoke-gqa":
        "14390a5d0ee7a736a8a85f78eca534f611643a129afa86e3093771f6f7121014",
    "slice-glm4-16-64":
        "75417230e9f9042e4515b96db33d791e98eb7476f87592ae381d1d7ed9840a67",
    "slice-glm4-16-64-noembed":
        "363739b8f1151e4417716ad86e89807691e8a99de877ef7fb9035dc118148fcc",
    "slice-starcoder2-window":
        "ae1336dfcd8db156caa3639dbdd2e7606f008cfbcf5e978d347b48533ff9e782",
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_graph_fingerprint(case):
    assert fingerprint(CASES[case]()) == EXPECTED[case]
