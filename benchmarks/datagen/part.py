"""PART (clause 4.2.3): P_NAME is five distinct words of the 92-colour list
(clause 4.2.2.13, P_NAMES), P_RETAILPRICE the clause's formula of the key
(the one L_EXTENDEDPRICE is reckoned from), types and containers the
clause's syllables."""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from benchmarks.datagen import common as c
from benchmarks.datagen.lineitem import retail_price

COLOURS = ("almond antique aquamarine azure beige bisque black blanched blue "
           "blush brown burlywood burnished chartreuse chiffon chocolate "
           "coral cornflower cornsilk cream cyan dark deep dim dodger drab "
           "firebrick floral forest frosted gainsboro ghost goldenrod green "
           "grey honeydew hot indian ivory khaki lace lavender lawn lemon "
           "light lime linen magenta maroon medium metallic midnight mint "
           "misty moccasin navajo navy olive orange orchid pale papaya peach "
           "peru pink plum powder puff purple red rose rosy royal saddle "
           "salmon sandy seashell sienna sky slate smoke snow spring steel "
           "tan thistle tomato turquoise violet wheat white yellow").split()
TYPES = [" ".join((a, b, m))
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for m in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [" ".join((a, b)) for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
WORDS_A_NAME = 5


def name_words(rng, n):
    """[n, 5] indices into COLOURS, no word twice in a row of it: the first
    five places of a random order of the list."""
    return np.argsort(rng.random((n, len(COLOURS)), np.float32),
                      axis=1)[:, :WORDS_A_NAME]


def generate(sf, seed, made):
    n = c.rows("part", sf)
    rng = c.stream(seed, 6)
    key = np.arange(1, n + 1, dtype=np.int64)
    words = name_words(rng, n)
    mfgr = rng.integers(1, 6, n)
    brand = 10 * mfgr + rng.integers(1, 6, n)
    return pa.table({
        "p_partkey": pa.array(key),
        "p_name": pc.binary_join_element_wise(
            *[c.pick(COLOURS, words[:, j]) for j in range(WORDS_A_NAME)],
            " "),
        "p_mfgr": c.pick([f"Manufacturer#{m}" for m in range(1, 6)],
                         mfgr - 1),
        "p_brand": c.pick([f"Brand#{b}" for b in range(11, 56)], brand - 11),
        "p_type": c.pick(TYPES, rng.integers(0, len(TYPES), n)),
        "p_size": pa.array(rng.integers(1, 51, n, dtype=np.int32)),
        "p_container": c.pick(CONTAINERS,
                              rng.integers(0, len(CONTAINERS), n)),
        "p_retailprice": c.decimal_from_unscaled(retail_price(key), 12, 2),
        "p_comment": c.text(rng, seed, n, 5, 22),
    })
