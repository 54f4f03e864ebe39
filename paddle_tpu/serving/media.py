"""A request's images on the host: from the ``images`` field of a
``:generate`` body to what ``DecodeEngine.submit(media=)`` takes. Pixels
arrive RAW (no codec here): ``{"grid": [h, w], "pixels": "<base64 of uint8
(patch h, patch w, 3)>"}``, ``patch`` the pixels a patch's side holds (the
model's encoder says: ``DecodeModel.encoder``). Runs on the handler's
thread, outside the engine's lock."""
import base64
import binascii

import numpy as np

__all__ = ["decode_images"]


def decode_images(images, encoder):
    """``images`` (the body's list) -> ``[(patches uint8 (h w, patch
    width), (h, w))]`` as the encoder's program is fed them. ``ValueError``
    in words for an entry of another form, a grid the encoder does not take
    (``MediaEncoder.check_grid``: said before any pixel is decoded), pixels
    that are not base64 or not as many as the grid says. How many images a
    request may carry, and the prompt's marked positions, are the engine's
    to check (``DecodeEngine.submit``)."""
    if not isinstance(images, list):
        raise ValueError("images must be a list, got %s"
                         % type(images).__name__)
    out, side = [], encoder.patch
    for k, im in enumerate(images):
        try:
            h, w = (int(v) for v in im["grid"])
            raw = im["pixels"]
        except (KeyError, TypeError, ValueError):
            raise ValueError(
                'image %d: want {"grid": [h, w], "pixels": "<base64>"}' % k)
        try:
            encoder.check_grid(h, w)
            raw = base64.b64decode(raw, validate=True)
        except (binascii.Error, TypeError) as e:
            raise ValueError("image %d: pixels are not base64 (%s)" % (k, e))
        except ValueError as e:
            raise ValueError("image %d: %s" % (k, e))
        want = side * h * side * w * 3
        if len(raw) != want:
            raise ValueError(
                "image %d: %d bytes of pixels; a grid of %d x %d patches of "
                "%d x %d x 3 uint8 is %d" % (k, len(raw), h, w, side, side,
                                             want))
        pixels = np.frombuffer(raw, np.uint8).reshape(side * h, side * w, 3)
        out.append((encoder.patchify(pixels), (h, w)))
    return out
