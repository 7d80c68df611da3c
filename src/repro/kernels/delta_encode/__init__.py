from .ops import (
    delta_zigzag,
    delta_zigzag_varint,
    uvarint_encode64,
)

__all__ = ["delta_zigzag", "delta_zigzag_varint", "uvarint_encode64"]
