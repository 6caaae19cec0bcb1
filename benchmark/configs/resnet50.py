"""ResNet-50 (He et al. 2015, Table 1) as the benchmark runs it.

Everything that belongs to this configuration and is code: how the program
is built, how a batch is made from a key, what one sample is, how many
operations one sample needs, and the plain float32 reference forward. The
sizes are in resnet50.json beside this file; nothing here is a size.
"""
import jax
import jax.numpy as jnp

from benchmark import checks

SAMPLE = "image"
check = checks.training


def build(fluid, cfg, traffic):
    """Build the training program in the current program guard; returns
    what every step fetches, the loss. `traffic["build"]` may override the
    learning rate (the linear scaling rule of a larger global batch) and ask
    for uint8 input."""
    from paddle_tpu.models.image_classification import build_train
    over = traffic.get("build", {})
    hw = cfg["image_hw"]
    _, _, avg_cost, _ = build_train(
        model=cfg["model"], class_dim=cfg["class_dim"],
        image_shape=(cfg["image_channels"], hw, hw),
        learning_rate=over.get("learning_rate", cfg["learning_rate"]),
        momentum=cfg["momentum"], use_bf16=True,
        uint8_input=traffic.get("feed") == "host_u8")
    return {"loss": avg_cost}


def samples_per_step(cfg, traffic):
    return traffic["batch"]


def make_batch(cfg, traffic, key):
    """The feed of one step, from a key; traced inside one jitted call."""
    b, hw = traffic["batch"], cfg["image_hw"]
    k_img, k_lbl = jax.random.split(key)
    return {
        "image": jax.random.uniform(
            k_img, (b, cfg["image_channels"], hw, hw), jnp.float32),
        "label": jax.random.randint(
            k_lbl, (b, 1), 0, cfg["class_dim"], jnp.int32)}


def host_batches(cfg, traffic, rng, n):
    """`n` numpy batches with uint8 images for the host-fed traffic."""
    b, hw = traffic["batch"], cfg["image_hw"]
    return [{
        "image": (rng.rand(b, cfg["image_channels"], hw, hw)
                  * 255).astype("uint8"),
        "label": rng.randint(0, cfg["class_dim"], (b, 1)).astype("int32")}
        for _ in range(n)]


def _convs(cfg):
    """(c_in, c_out, kernel, out_hw) of every convolution, in the order the
    program creates them, then the classifier as (c_in, c_out, 1, 1)."""
    hw = cfg["image_hw"] // 2                     # 7x7 stride 2
    out = [(cfg["image_channels"], cfg["stem_filters"], 7, hw)]
    hw //= 2                                      # 3x3 max pool stride 2
    c_in, exp = cfg["stem_filters"], cfg["bottleneck_expansion"]
    for stage, (n, f) in enumerate(zip(cfg["blocks_per_stage"],
                                       cfg["stage_filters"])):
        for i in range(n):
            stride = 2 if i == 0 and stage > 0 else 1
            out.append((c_in, f, 1, hw))          # 1x1 at the input size
            hw //= stride                         # the 3x3 carries the stride
            out.append((f, f, 3, hw))
            out.append((f, f * exp, 1, hw))
            if c_in != f * exp or stride != 1:
                out.append((c_in, f * exp, 1, hw))    # projection shortcut
            c_in = f * exp
    out.append((c_in, cfg["class_dim"], 1, 1))
    return out


def ops_per_sample(cfg, traffic):
    """Floating-point operations the forward and backward passes need for
    one image: two a multiply-add, over every convolution and the
    classifier, three times (forward, gradient to the input, gradient to
    the weights). Batch norm, activations, pooling and the optimizer are
    not counted; nor is it taken off that the first convolution needs no
    gradient to its input (1.4 % of the total). 24.53e9 at the paper's
    sizes, 3 x 8.18e9."""
    macs = sum(ci * co * k * k * hw * hw for ci, co, k, hw in _convs(cfg))
    return 3 * 2 * macs


def reference(cfg, traffic, params, batch):
    """What `build` fetches (the mean cross-entropy), from the plain forward
    pass in float32: no AMP, no
    kernel, batch statistics in every batch norm (training mode). `params`
    are the program's parameters in the order it created them: for each
    convolution its weight [O, I, k, k], then the batch norm's scale, shift,
    moving mean and moving variance (the last two unused here); last the
    classifier's weight [C, classes] and bias."""
    params = list(params)
    pos = [0]

    def take(n):
        got = params[pos[0]:pos[0] + n]
        pos[0] += n
        return got

    def conv_bn(x, stride, relu):
        w, scale, shift, _, _ = take(5)
        pad = (w.shape[-1] - 1) // 2
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        mean = y.mean(axis=(0, 2, 3), keepdims=True)
        var = jnp.square(y - mean).mean(axis=(0, 2, 3), keepdims=True)
        y = (y - mean) * jax.lax.rsqrt(var + 1e-5)
        y = y * scale[None, :, None, None] + shift[None, :, None, None]
        return jax.nn.relu(y) if relu else y

    with jax.default_matmul_precision("highest"):
        x = batch["image"]
        if x.dtype == jnp.uint8:        # the host_u8 feed: scaled on device
            x = x.astype(jnp.float32) / 255.0
        x = conv_bn(x, 2, True)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            [(0, 0), (0, 0), (1, 1), (1, 1)])
        exp = cfg["bottleneck_expansion"]
        for stage, (n, f) in enumerate(zip(cfg["blocks_per_stage"],
                                           cfg["stage_filters"])):
            for i in range(n):
                stride = 2 if i == 0 and stage > 0 else 1
                y = conv_bn(x, 1, True)
                y = conv_bn(y, stride, True)
                y = conv_bn(y, 1, False)
                if x.shape[1] != f * exp or stride != 1:
                    x = conv_bn(x, stride, False)
                x = jax.nn.relu(x + y)
        x = x.mean(axis=(2, 3))
        w, b = take(2)
        logp = jax.nn.log_softmax(x @ w + b, axis=-1)
    if pos[0] != len(params):
        raise ValueError("the reference read %d parameters, the program has "
                         "%d: the two are not the same architecture"
                         % (pos[0], len(params)))
    label = batch["label"].reshape(-1)
    return {"loss": -jnp.take_along_axis(logp, label[:, None],
                                         axis=1).mean()}
