"""The benchmark's lenet5 and resnet8 programs, pinned byte for byte.

Both nets share the compiler with every net added after them (the graph
passes, ``compile_layer``, ``gemm_compiler``'s chunking and ALU
programs).  Built as the benchmark builds them (``bench/configs/<c>.py``
on weight seed 0), their DRAM image — data, micro-ops and instructions of
every layer — and each layer's instruction bytes must keep the SHA-256 they
had when ResNet-50's lowering came in: a change to shared code that moves
either one byte changes what the benchmark's existing cells run.

Hypothesis-free: part of the tier-1 floor.
"""

import hashlib

import pytest

from bench import layout

PINS = {
    "lenet5.online": (
        "2c7295ddc5a895d07ea3c0eceacf0c539ade63baa78cf48520c7e28c59a25107",
        ["05dde33e0601f6de97ab24879099ac63a58892c3b43ea565c49c9bd9516295cd",
         "cd3664e1de643585dd38f140bcd9d9ab6e67245fc462caf55cae7d1d4e917356",
         "adf8817274f7ee6c20ef4d4ff40065a2a29b5a8486c0a81dba80e0e23470cf68",
         "ee8b33f5e33227dc166d88b8da3dc8fe78c0bdfd1f7d29ff791b46d99a59aa9d",
         "1d457b4fabe2b4233131a91862125a33f6196ab3a668a2ef0c7f087709f6456d"]),
    "resnet8.offline": (
        "0fe0fd40f47e87e8c7d47d1714df4d224d07d17604648fd97208cd2abb48d33c",
        ["cff4214c445622b134bf4bca9d2a62c6215d4559dc6ed5c70bb858f9bc5f5b52",
         "8fd65ba242f241ce06d25c5a2d18b49c42895cd606ba13ec683e924c6d73f91f",
         "11fb416e8ea1cc40f01faeebb1f95b13c3a800ec29c68c664526bd885adc3a76",
         "d4e38f1fcf43819c161a3439b916f909e900ab8f86a34b85808ffa79651995d6",
         "2d1eaa4597ade2174d39db59d6753796aed9871067a855c17c12b3f85d88fd5c",
         "fc8df558ce94066fbf32353dd7101e55d54373ef2c8c259d3907794802c74a95",
         "53d19a1eef0f54d4bc494c9a32f2a5c2bdc65fcc24b4581dd0637696e96c9dba",
         "0c94a4a2a051815dfc0c3d5a67fbbd5fab1ca89e79e08d75ec6a33362c96994a",
         "35c39a7356423cc533a08b331bc0a8810dba8ca4f2bb2ccfe34f1853b8c3c703",
         "45e087d5e23182f12d6483796a4782cfac6c1679bf0e52e65a681c9abfa84197",
         "aa8d7b86e5ff5358f66fe2eb2b5ce6bdaf7cd7996695ca0f874090f8597bd8d1"]),
}


def _sha(data) -> str:
    return hashlib.sha256(bytes(data)).hexdigest()


@pytest.mark.parametrize("cell", sorted(PINS))
def test_benchmark_nets_compile_to_the_pinned_bytes(cell):
    c = layout.load_cell(cell)
    net = c.net_module().build(c.config, c.ref_module().weights(c.config, 0))
    dram, insns = PINS[cell]
    assert [_sha(layer.program.segments["insn"])
            for layer in net.layers] == insns
    assert _sha(net.dram_image()) == dram
