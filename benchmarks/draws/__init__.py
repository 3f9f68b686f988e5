"""How one leaf of a batch is drawn, one module per ``draw`` of a cell's
``fields``: ``draw(rng, field, resolve)`` -> numpy array, where
``resolve(symbol)`` gives the number a symbol of the cell stands for. The
shape never depends on the values drawn. A new draw is a new file here."""
