"""Operations and bytes an algorithm needs, from shapes alone.

Every function here is the benchmark's yardstick for a roofline or MFU share:
it counts what the mathematics requires, not what a program happens to do, so
recomputation or padding in the program never raises the count.
"""


def conv_flops(h_out, w_out, kh, kw, cin, cout):
    """Multiply-adds of one convolution, counted as 2 operations each."""
    return 2 * h_out * w_out * kh * kw * cin * cout


def resnet_flops_per_image(stage_sizes, width, num_classes, image):
    """Forward FLOPs of a bottleneck ResNet v1.5 on one ``image`` x ``image``
    x 3 input: He et al. 2015, Table 1, with the stride of a down-sampling
    block on its 3x3 convolution and not on the first 1x1 (the form of
    torchvision's and Hugging Face's ``microsoft/resnet-50``: 4.09 against
    v1's 3.8 billion multiply-adds at 224). Convolutions and the classifier;
    elementwise work (ReLU, adds, pooling) is left out, as is usual."""
    side = image // 2                      # stem: 7x7, stride 2
    total = conv_flops(side, side, 7, 7, 3, width)
    side //= 2                             # 3x3 max-pool, stride 2
    cin = width
    for si, nblocks in enumerate(stage_sizes):
        cmid = width * 2 ** si
        cout = 4 * cmid
        for bi in range(nblocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            total += conv_flops(side, side, 1, 1, cin, cmid)
            side_out = side // stride      # the 3x3 carries the stride
            total += conv_flops(side_out, side_out, 3, 3, cmid, cmid)
            total += conv_flops(side_out, side_out, 1, 1, cmid, cout)
            if bi == 0:
                total += conv_flops(side_out, side_out, 1, 1, cin, cout)
            side, cin = side_out, cout
    return total + 2 * cin * num_classes


def kv_bytes_per_token(layers, d_model, bytes_per_value):
    """Bytes of K and V one cached position holds over all layers."""
    return layers * 2 * d_model * bytes_per_value


def paged_attention_bytes(live_positions, layers, d_model, bytes_per_value):
    """Least bytes decode attention must read from the KV cache to emit one
    token for every sequence of a tick: each live position's K and V, once.
    ``live_positions`` is the sum over sequences of their cached lengths."""
    return live_positions * kv_bytes_per_token(layers, d_model,
                                               bytes_per_value)


def histogram_bytes(rows, features, grad_channels, bin_bytes=1, grad_bytes=4):
    """Least bytes one histogram build over ``rows`` must read: every row's
    binned features and its gradient channels. The histogram written is
    small beside it and left out."""
    return rows * (features * bin_bytes + grad_channels * grad_bytes)


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound sets it)."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
