SELECT
  distinct_count(category) OVER w_union_3s AS product_count,
  avg_cate_where(price, quantity > 1, category)
      OVER w_union_3s AS product_prices,
  sum(price) OVER w_action_100d AS spend_100d,
  topn_frequency(category, 3) OVER w_action_100d AS favourite_products,
  profile.age AS age,
  price * quantity AS order_value
FROM actions
LAST JOIN profile ORDER BY ts ON actions.userid = profile.userid
WINDOW w_union_3s AS (UNION orders PARTITION BY userid ORDER BY ts
                      ROWS_RANGE BETWEEN 3s PRECEDING AND CURRENT ROW),
      w_action_100d AS (PARTITION BY userid ORDER BY ts
                        ROWS_RANGE BETWEEN 100d PRECEDING AND CURRENT ROW)
