"""The paper's benchmark CNNs — plus sequence models — as builder programs.

The CNNs are the CIFAR-10 variants the paper §IV evaluates; the
simulator, the scheduler and the compiled-program path all read their
``LayerSpec`` lists from these graphs, and ``NetworkGraph.forward`` is
their functional oracle.

``vit_tiny`` opens the transformer workload class (DESIGN.md §9): a
patchify conv, ``depth`` post-norm encoder blocks (attention + MLP,
each ``x = LN(x + f(x))``), and a mean-pooled classifier head — every
block built from the sequence ops the crossbar program stack lowers
(attention expands into dynamic-operand GEMM stages).  The default is a
CI-scale reduction (2 blocks of the ViT-Tiny geometry: dim 192, 3
heads, MLP ratio 4); pass ``depth=12`` for the full-size model.

``deit_ti`` is DeiT-Ti at 224x224 as published: pre-norm blocks, a
class token and a position table (``embed``), exact GELU, LN epsilon
1e-6, and a head on the class token.

``swin_t`` is Swin-T at 224x224 as published: a normed 4x4 patch
embedding, four stages of shifted-window attention blocks joined by
patch merging, and a mean-pooled head.
"""

from __future__ import annotations

from .graph import NetworkBuilder, NetworkGraph


def alexnet_graph() -> NetworkGraph:
    nb = NetworkBuilder("alexnet", input_hw=32, input_ch=3)
    for i, (ch, pool) in enumerate([(64, True), (192, True), (384, False),
                                    (256, False), (256, True)], 1):
        nb.conv(ch, name=f"conv{i}")
        nb.relu(name=f"relu{i}")
        if pool:
            nb.maxpool(name=f"pool{i}")
    # CIFAR-scale classifier (1024-unit FC variant commonly used for
    # AlexNet-CIFAR; the ImageNet 4096-unit head would dwarf the convs)
    nb.fc(1024, name="fc6")
    nb.relu(name="relu6")
    nb.fc(1024, name="fc7")
    nb.relu(name="relu7")
    nb.fc(10, name="fc8")
    nb.softmax(name="softmax")
    return nb.build()


def vgg16_graph() -> NetworkGraph:
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
           512, 512, 512, "M", 512, 512, 512, "M"]
    nb = NetworkBuilder("vgg16", input_hw=32, input_ch=3)
    i = 1
    for v in cfg:
        if v == "M":
            nb.maxpool(name=f"pool{i}")
        else:
            nb.conv(v, name=f"conv{i}")
            nb.relu(name=f"relu{i}")
            i += 1
    nb.fc(512, name="fc1")
    nb.relu(name="relu_fc1")
    nb.fc(10, name="fc2")
    nb.softmax(name="softmax")
    return nb.build()


def resnet18_graph() -> NetworkGraph:
    nb = NetworkBuilder("resnet18", input_hw=32, input_ch=3)
    nb.conv(64, name="conv0")
    entry = nb.relu(name="relu0")     # block input = prev block's output
    in_ch = 64
    for stage, ch in enumerate((64, 128, 256, 512)):
        for b in range(2):
            s = 2 if (stage > 0 and b == 0) else 1
            n = f"s{stage}b{b}"
            res_src = entry           # identity shortcut unless projected
            if in_ch != ch:
                # 1x1 projection on the shortcut (its own GEMM group)
                res_src = nb.conv(ch, k=1, stride=s, padding=0,
                                  name=f"{n}_proj", input_from=entry)
            nb.conv(ch, stride=s, name=f"{n}_conv1", input_from=entry)
            nb.relu(name=f"{n}_relu1")
            nb.conv(ch, name=f"{n}_conv2")
            nb.residual(res_src, name=f"{n}_res")
            entry = nb.relu(name=f"{n}_relu2")
            in_ch = ch
    nb.avgpool(k=4, stride=4, name="avgpool")
    nb.fc(10, name="fc")
    nb.softmax(name="softmax")
    return nb.build()


def vit_tiny_graph(depth: int = 2, dim: int = 192, heads: int = 3,
                   mlp_ratio: int = 4, patch: int = 4, input_hw: int = 32,
                   input_ch: int = 3, classes: int = 10) -> NetworkGraph:
    """Patchify conv + ``depth`` post-norm encoder blocks + pooled head.

    CIFAR-scale ViT: a ``patch x patch`` stride-``patch`` conv rasterizes
    the image into ``(input_hw/patch)^2`` tokens of dim ``dim``; each
    encoder block is ``x = LN(x + MHA(x)); x = LN(x + MLP(x))``
    (post-norm, so both normalizations are FB post-ops of their
    residual's GEMM stage); the head mean-pools the tokens and
    classifies.  Attention lowers into the dynamic-operand GEMM stages
    of DESIGN.md §9.
    """
    nb = NetworkBuilder("vit_tiny", input_hw=input_hw, input_ch=input_ch)
    if input_hw % patch:
        raise ValueError(f"vit_tiny: patch {patch} does not tile "
                         f"{input_hw}x{input_hw}")
    entry = nb.conv(dim, k=patch, stride=patch, padding=0, name="patch")
    for i in range(depth):
        nb.attention(heads, name=f"b{i}_attn")
        nb.residual(entry, name=f"b{i}_res1")
        r1 = nb.layernorm(name=f"b{i}_ln1")
        nb.linear(dim * mlp_ratio, name=f"b{i}_fc1")
        nb.gelu(name=f"b{i}_gelu")
        nb.linear(dim, name=f"b{i}_fc2")
        nb.residual(r1, name=f"b{i}_res2")
        entry = nb.layernorm(name=f"b{i}_ln2")
    nb.seqpool(name="pool")
    nb.fc(classes, name="head")
    nb.softmax(name="softmax")
    return nb.build()


vit_tiny = vit_tiny_graph


def deit_graph(depth: int = 12, dim: int = 192, heads: int = 3,
               mlp_ratio: int = 4, patch: int = 16, input_hw: int = 224,
               input_ch: int = 3, classes: int = 1000, eps: float = 1e-6,
               name: str = "deit_ti") -> NetworkGraph:
    """DeiT-Ti as published (Touvron et al., arXiv:2012.12877, Table 1;
    the block equations of ViT, arXiv:2010.11929, Eqs. 1-4)::

        z0  = [x_cls; patches(x) E] + E_pos     # hw^2 + 1 tokens
        z'l = z(l-1) + MSA(LN1(z(l-1)))
        zl  = z'l + MLP(LN2(z'l))               # GELU (erf)
        p   = softmax(head(LN(zL[0])))          # the class token

    Pre-norm blocks: every layer norm normalizes a GEMM op's input
    (``layernorm(pre=True)``), so the residual stream stays un-normed;
    LN epsilon 1e-6 and exact GELU as in timm's
    ``deit_tiny_patch16_224``.  The defaults are DeiT-Ti at 224x224:
    197 tokens of width 192, 3 heads of 64, MLP 768, 1000 classes.
    """
    if input_hw % patch:
        raise ValueError(f"{name}: patch {patch} does not tile "
                         f"{input_hw}x{input_hw}")
    nb = NetworkBuilder(name, input_hw=input_hw, input_ch=input_ch)
    nb.conv(dim, k=patch, stride=patch, padding=0, name="patch")
    entry = nb.embed(name="embed")
    for i in range(depth):
        nb.layernorm(pre=True, eps=eps, name=f"b{i}_ln1")
        nb.attention(heads, name=f"b{i}_attn")
        r1 = nb.residual(entry, name=f"b{i}_res1")
        nb.layernorm(pre=True, eps=eps, name=f"b{i}_ln2")
        nb.linear(dim * mlp_ratio, name=f"b{i}_fc1")
        nb.gelu(approx="erf", name=f"b{i}_gelu")
        nb.linear(dim, name=f"b{i}_fc2")
        entry = nb.residual(r1, name=f"b{i}_res2")
    nb.seqpool(mode="cls", name="pool")
    nb.layernorm(pre=True, eps=eps, name="norm")
    nb.fc(classes, name="head")
    nb.softmax(name="softmax")
    return nb.build()


def swin_graph(depths: tuple[int, ...] = (2, 2, 6, 2),
               heads: tuple[int, ...] = (3, 6, 12, 24), dim: int = 96,
               window: int = 7, patch: int = 4, mlp_ratio: int = 4,
               input_hw: int = 224, input_ch: int = 3, classes: int = 1000,
               eps: float = 1e-5, name: str = "swin_t") -> NetworkGraph:
    """Swin Transformer (Liu et al., arXiv:2103.14030, §3.1-3.3;
    timm's ``swin_tiny_patch4_window7_224`` by default)::

        z   = LN(patches(x) E)                   # (input_hw/patch)^2 tokens
        per stage s (width dim * 2^s):
          z = Linear(LN(merge2x2(z)))            # s > 0: patch merging
          per block i:
            z = z + W-MSA(LN1(z))                # shift M//2 on odd i
            z = z + MLP(LN2(z))                  # GELU (erf)
        p   = softmax(head(mean(LN(z))))

    Attention runs inside M x M windows of the token map with a relative
    position bias; every odd block rolls the map by M//2 first (none
    where the window covers the map).  Merging concatenates each 2x2
    neighbourhood (4C), normalizes it and maps it to 2C without a bias.
    The defaults are Swin-T: C = 96, depths {2, 2, 6, 2}, heads {3, 6,
    12, 24} (head dim 32), window 7, MLP ratio 4, LN epsilon 1e-5.
    """
    if input_hw % patch:
        raise ValueError(f"{name}: patch {patch} does not tile "
                         f"{input_hw}x{input_hw}")
    nb = NetworkBuilder(name, input_hw=input_hw, input_ch=input_ch)
    nb.conv(dim, k=patch, stride=patch, padding=0, name="patch")
    entry = nb.layernorm(eps=eps, name="patch_norm")
    for s, (depth, h) in enumerate(zip(depths, heads)):
        if s:
            nb.layernorm(pre=True, eps=eps, name=f"s{s}_merge_norm")
            dim *= 2
            entry = nb.linear(dim, merge=True, bias=False,
                              name=f"s{s}_merge")
        for i in range(depth):
            n = f"s{s}b{i}"
            nb.layernorm(pre=True, eps=eps, name=f"{n}_ln1")
            nb.attention(h, window=window, shift=window // 2 if i % 2 else 0,
                         name=f"{n}_attn")
            r1 = nb.residual(entry, name=f"{n}_res1")
            nb.layernorm(pre=True, eps=eps, name=f"{n}_ln2")
            nb.linear(dim * mlp_ratio, name=f"{n}_fc1")
            nb.gelu(approx="erf", name=f"{n}_gelu")
            nb.linear(dim, name=f"{n}_fc2")
            entry = nb.residual(r1, name=f"{n}_res2")
    nb.layernorm(eps=eps, name="norm")
    nb.seqpool(mode="mean", name="pool")
    nb.fc(classes, name="head")
    nb.softmax(name="softmax")
    return nb.build()


GRAPHS = {
    "alexnet": alexnet_graph,
    "vgg16": vgg16_graph,
    "resnet18": resnet18_graph,
    "vit_tiny": vit_tiny_graph,
    "deit_ti": deit_graph,
    "swin_t": swin_graph,
}
